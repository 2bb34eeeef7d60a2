"""FSDP gather with compressed-gradient backward (the "cotangent hijack").

PyTorch LoCo hooks the FSDP reduce-scatter during backward.  The JAX
equivalent: a ``custom_vjp`` whose forward is the FSDP ``all_gather`` of a
flat parameter chunk, and whose backward replaces the autodiff transpose
(full-precision reduce-scatter) with LoCo's compensate -> quantize ->
all_to_all -> dequant-mean.  The updated compensation-error buffer is
returned as the *cotangent of the error input* -- legal because the error
is stored in a float dtype (f8_e4m3 / bf16), so primal and cotangent dtypes
match and ``jax.grad(loss, argnums=(params, errors))`` yields
``(grad_shards, new_errors)`` in a single backward pass, layer by layer
inside the backward scan (grad buffers freed as in real FSDP).

See DESIGN.md §3 for the full rationale.
"""
from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp

from repro.core.buckets import ParamPlan
from repro.core.comm import (_fit_rows, all_gather_flat, axis_size,
                             dist_sync, dist_sync_buckets, dist_sync_runs,
                             psum_scatter_flat)
from repro.core.loco import SyncConfig
from repro.telemetry import profiler as PROF


def _reject_stochastic_rounding(cfg: SyncConfig) -> None:
    """The hijack backward has no PRNG-key input, so stochastic rounding
    cannot run here — fail loudly at build time instead of silently
    rounding to nearest (regression: tests/test_codec.py)."""
    if cfg.strategy != "fp" and cfg.quant.stochastic_rounding:
        raise ValueError(
            "QuantConfig.stochastic_rounding is not supported on the "
            "in-backward hijack path (the custom_vjp backward has no PRNG "
            "key to thread); use the post-grad dist_sync/sim_sync with an "
            "explicit key, or disable stochastic_rounding."
        )


def _as_step(step) -> jax.Array:
    """Normalize the optional step index to the traced f32 scalar the
    custom_vjp closures thread.

    The step must ride as a *primal* (``nondiff_argnums`` would force a
    retrace per step value — exactly what the cadence gate exists to
    avoid), and f32 keeps the cotangent dtype trivially legal; exact for
    any realistic step count (< 2^24).  ``None`` maps to step 0, which is
    bit-transparent for ``every == 1`` configs (the universal default) —
    cadence plans must thread the real step (launch/steps.py does).
    """
    return jnp.float32(0.0) if step is None else jnp.asarray(step, jnp.float32)


def _gather(w_chunk: jax.Array, dp_axes: tuple[str, ...]) -> jax.Array:
    """The forward weight all-gather of every hijack below, under the
    ``loco/gather`` scope.  The scope sits here and not on
    ``comm.all_gather_flat``, which the exchange also calls."""
    with PROF.phase("gather"):
        return all_gather_flat(w_chunk, dp_axes)


@lru_cache(maxsize=None)
def _make_gather(cfg: SyncConfig, dp_axes: tuple[str, ...]):
    """Build (and cache) the custom_vjp gather for a given static config."""
    _reject_stochastic_rounding(cfg)

    @jax.custom_vjp
    def gather(w_chunk: jax.Array, state: jax.Array,
               step: jax.Array) -> jax.Array:
        return _gather(w_chunk, dp_axes)

    def fwd(w_chunk, state, step):
        return _gather(w_chunk, dp_axes), (state, step)

    def bwd(res, g_full):
        state, step = res
        # chunk dtype == gathered dtype, so g_full.dtype is the right
        # cotangent dtype for w_chunk.
        g_shard, new_state = dist_sync(g_full, state, cfg, dp_axes, step=step)
        return (g_shard.astype(g_full.dtype), new_state.astype(state.dtype),
                jnp.zeros_like(step))

    gather.defvjp(fwd, bwd)
    return gather


def gather_with_sync(
    w_chunk: jax.Array,
    state: jax.Array,
    cfg: SyncConfig,
    dp_axes: tuple[str, ...],
    step: jax.Array | None = None,
) -> jax.Array:
    """FSDP all-gather whose backward runs the configured sync strategy.

    w_chunk: (n/D,) local flat parameter chunk (bf16 recommended on the wire)
    state:   per-device compressor state, shape (n,) (full local-gradient
             size) in a float dtype; its cotangent carries the new state.
    step:    optional traced step index for the cadence gate (see
             comm.dist_sync); defaults to step 0.
    """
    assert jnp.issubdtype(state.dtype, jnp.floating), (
        "hijack state must be a float dtype (f8/bf16/f32) so its cotangent "
        "can carry the updated state; int8 error storage is only available "
        "in the post-grad reference path"
    )
    return _make_gather(cfg, tuple(dp_axes))(w_chunk, state, _as_step(step))


@lru_cache(maxsize=None)
def _make_bucketed_gather(plan: ParamPlan, dp_axes: tuple[str, ...],
                          coalesce: bool = True, overlap: bool = False):
    """custom_vjp gather whose backward runs the per-bucket schedule.

    The compressor state is a *tuple* of per-bucket buffers; the tuple rides
    through the custom_vjp as one pytree argument, and the backward returns
    the per-bucket updated states as its cotangent (same float-dtype
    legality argument as the monolithic path — see module docstring).

    ``coalesce`` selects the packed one-collective-per-comm-group exchange
    (default; bit-exact with the per-bucket schedule, see DESIGN.md §13);
    ``overlap`` additionally pipelines the packed stages (DESIGN.md §15).
    Both flags are part of the cache key so a ``--no-coalesce`` /
    ``--no-overlap`` run never reuses the wrong closure.
    """
    for b in plan.buckets:
        _reject_stochastic_rounding(b.sync)

    @jax.custom_vjp
    def gather(w_chunk: jax.Array, states: tuple,
               step: jax.Array) -> jax.Array:
        return _gather(w_chunk, dp_axes)

    def fwd(w_chunk, states, step):
        return _gather(w_chunk, dp_axes), (states, step)

    def bwd(res, g_full):
        states, step = res
        g_shard, new_states = dist_sync_buckets(g_full, states, plan, dp_axes,
                                                coalesce=coalesce,
                                                overlap=overlap, step=step)
        new_states = tuple(ns.astype(s.dtype)
                           for ns, s in zip(new_states, states))
        return (g_shard.astype(g_full.dtype), new_states,
                jnp.zeros_like(step))

    gather.defvjp(fwd, bwd)
    return gather


def gather_with_sync_buckets(
    w_chunk: jax.Array,
    states: tuple[jax.Array, ...],
    plan: ParamPlan,
    dp_axes: tuple[str, ...],
    coalesce: bool = True,
    overlap: bool = False,
    step: jax.Array | None = None,
) -> jax.Array:
    """FSDP all-gather whose backward runs the bucketed sync schedule.

    w_chunk: (C,) local flat parameter chunk (C = plan.chunklen)
    states:  per-bucket compressor states, bucket b's shaped (seg_elems,)
             in its resolved state dtype (or a (1,) dummy when stateless).
    """
    for st, b in zip(states, plan.buckets):
        assert jnp.issubdtype(st.dtype, jnp.floating), (
            f"bucket {b.index} state must be a float dtype for the "
            "cotangent to carry the updated state (see gather_with_sync)")
    return _make_bucketed_gather(plan, tuple(dp_axes), coalesce,
                                 overlap)(w_chunk, tuple(states),
                                          _as_step(step))


@lru_cache(maxsize=None)
def _make_run_gather(plan: ParamPlan, dp_axes: tuple[str, ...],
                     overlap: bool = False, piece_space: bool = False):
    """custom_vjp gather whose backward runs the coalesced schedule with
    RUN-space states (one buffer per encode run — see
    :func:`repro.core.flatparam.fuse_run_states`).  The training hot path
    uses this form: the state pytree that rides the scan carries and the
    cotangent shrinks from len(buckets) to len(runs) leaves.

    ``overlap`` (cache-keyed, like ``coalesce`` above) selects the
    pipelined stage schedule; the state layout is identical either way, so
    flipping it never reshapes checkpoints or retriggers retraces beyond
    the one new closure.  ``piece_space`` declares that the caller carries
    states in the schedule's piece layout (see
    :func:`repro.core.wirepack.state_pieces`) so the backward skips the
    in-graph run<->piece conversion — the training scan uses this to keep
    the per-microbatch graph free of low-bit slice/concat ops."""
    for b in plan.buckets:
        _reject_stochastic_rounding(b.sync)

    @jax.custom_vjp
    def gather(w_chunk: jax.Array, run_states: tuple,
               step: jax.Array) -> jax.Array:
        return _gather(w_chunk, dp_axes)

    def fwd(w_chunk, run_states, step):
        return _gather(w_chunk, dp_axes), (run_states, step)

    def bwd(res, g_full):
        run_states, step = res
        g_shard, new_states = dist_sync_runs(g_full, run_states, plan,
                                             dp_axes, overlap=overlap,
                                             piece_space=piece_space,
                                             step=step)
        new_states = tuple(ns.astype(s.dtype)
                           for ns, s in zip(new_states, run_states))
        return (g_shard.astype(g_full.dtype), new_states,
                jnp.zeros_like(step))

    gather.defvjp(fwd, bwd)
    return gather


def gather_with_sync_runs(
    w_chunk: jax.Array,
    run_states: tuple[jax.Array, ...],
    plan: ParamPlan,
    dp_axes: tuple[str, ...],
    overlap: bool = False,
    piece_space: bool = False,
    step: jax.Array | None = None,
) -> jax.Array:
    """FSDP all-gather whose backward runs the coalesced bucketed schedule
    over run-space compressor states (bit-exact with
    :func:`gather_with_sync_buckets` modulo the state view)."""
    for st in run_states:
        assert jnp.issubdtype(st.dtype, jnp.floating), (
            "run state must be a float dtype for the cotangent to carry "
            "the updated state (see gather_with_sync)")
    return _make_run_gather(plan, tuple(dp_axes), overlap,
                            piece_space)(w_chunk, tuple(run_states),
                                         _as_step(step))


# ---------------------------------------------------------------------------
# fidelity-probe gather variants (DESIGN.md §17)
# ---------------------------------------------------------------------------
#
# The probe step's gathers take one extra zeros primal (`probe`, fp32
# (K, chunklen)) whose COTANGENT carries the fidelity reference stack out
# of the backward — the same trick that carries the updated error state as
# the state input's cotangent.  The synced shard and new states are
# bit-identical to the non-probe gathers (comm computes them on the same
# path; pinned by tests/test_fidelity.py), so probing never perturbs the
# trajectory; the refs are *extra* outputs, invisible to the optimizer.

def _probe_cot(refs: jax.Array, probe: jax.Array) -> jax.Array:
    """Fit the backward's natural ref stack to the probe primal's static
    row count (padded rows stay zero for shallower stage schedules)."""
    return _fit_rows(refs, probe.shape[0]).astype(probe.dtype)


@lru_cache(maxsize=None)
def _make_gather_probe(cfg: SyncConfig, dp_axes: tuple[str, ...]):
    _reject_stochastic_rounding(cfg)

    @jax.custom_vjp
    def gather(w_chunk: jax.Array, state: jax.Array, probe: jax.Array,
               step: jax.Array) -> jax.Array:
        return _gather(w_chunk, dp_axes)

    def fwd(w_chunk, state, probe, step):
        return _gather(w_chunk, dp_axes), (state, probe, step)

    def bwd(res, g_full):
        state, probe, step = res
        g_shard, new_state, refs = dist_sync(g_full, state, cfg, dp_axes,
                                             step=step, probe=True)
        return (g_shard.astype(g_full.dtype), new_state.astype(state.dtype),
                _probe_cot(refs, probe), jnp.zeros_like(step))

    gather.defvjp(fwd, bwd)
    return gather


def gather_with_sync_probe(w_chunk, state, probe, cfg, dp_axes, step=None):
    """:func:`gather_with_sync` + fidelity refs as ``probe``'s cotangent."""
    return _make_gather_probe(cfg, tuple(dp_axes))(w_chunk, state, probe,
                                                   _as_step(step))


@lru_cache(maxsize=None)
def _make_bucketed_gather_probe(plan: ParamPlan, dp_axes: tuple[str, ...]):
    for b in plan.buckets:
        _reject_stochastic_rounding(b.sync)

    @jax.custom_vjp
    def gather(w_chunk: jax.Array, states: tuple, probe: jax.Array,
               step: jax.Array) -> jax.Array:
        return _gather(w_chunk, dp_axes)

    def fwd(w_chunk, states, probe, step):
        return _gather(w_chunk, dp_axes), (states, probe, step)

    def bwd(res, g_full):
        states, probe, step = res
        g_shard, new_states, refs = dist_sync_buckets(
            g_full, states, plan, dp_axes, coalesce=False, step=step,
            probe=True)
        new_states = tuple(ns.astype(s.dtype)
                           for ns, s in zip(new_states, states))
        return (g_shard.astype(g_full.dtype), new_states,
                _probe_cot(refs, probe), jnp.zeros_like(step))

    gather.defvjp(fwd, bwd)
    return gather


def gather_with_sync_buckets_probe(w_chunk, states, probe, plan, dp_axes,
                                   step=None):
    """Per-bucket (non-coalesced) probe gather — the escape-hatch schedule
    and the only one that can carry multi-tier (WAN) plans."""
    return _make_bucketed_gather_probe(plan, tuple(dp_axes))(
        w_chunk, tuple(states), probe, _as_step(step))


@lru_cache(maxsize=None)
def _make_run_gather_probe(plan: ParamPlan, dp_axes: tuple[str, ...]):
    for b in plan.buckets:
        _reject_stochastic_rounding(b.sync)

    @jax.custom_vjp
    def gather(w_chunk: jax.Array, run_states: tuple, probe: jax.Array,
               step: jax.Array) -> jax.Array:
        return _gather(w_chunk, dp_axes)

    def fwd(w_chunk, run_states, probe, step):
        return _gather(w_chunk, dp_axes), (run_states, probe, step)

    def bwd(res, g_full):
        run_states, probe, step = res
        # the probe variant always runs the FLAT coalesced schedule —
        # bit-exact with the pipelined one (DESIGN.md §15), and the flat
        # schedule has the pre-regroup wires in hand for the references
        g_shard, new_states, refs = dist_sync_runs(
            g_full, run_states, plan, dp_axes, overlap=False,
            piece_space=False, step=step, probe=True)
        new_states = tuple(ns.astype(s.dtype)
                           for ns, s in zip(new_states, run_states))
        return (g_shard.astype(g_full.dtype), new_states,
                _probe_cot(refs, probe), jnp.zeros_like(step))

    gather.defvjp(fwd, bwd)
    return gather


def gather_with_sync_runs_probe(w_chunk, run_states, probe, plan, dp_axes,
                                step=None):
    """:func:`gather_with_sync_runs` + fidelity refs as ``probe``'s
    cotangent (flat coalesced schedule, run-space states)."""
    return _make_run_gather_probe(plan, tuple(dp_axes))(
        w_chunk, tuple(run_states), probe, _as_step(step))


@lru_cache(maxsize=None)
def _make_gather_fp(dp_axes: tuple[str, ...]):
    """Build (and cache) the fp custom_vjp gather per dp-axes tuple.

    Cached like :func:`_make_gather`: gather_fp is called once per non-loco
    parameter per trace, and rebuilding the custom_vjp closure each call
    defeated JAX's function-identity caches (pinned by the retrace-count
    test in tests/test_comm_dist.py)."""

    @jax.custom_vjp
    def gather(w_chunk):
        return _gather(w_chunk, dp_axes)

    def fwd(w_chunk):
        return _gather(w_chunk, dp_axes), None

    def bwd(_, g_full):
        # bf16 wire (the "16-bit Adam" baseline of the paper); mean in f32.
        # chunk dtype == gathered dtype, so g_full.dtype is the right
        # cotangent dtype for w_chunk.
        D = axis_size(dp_axes)
        g = psum_scatter_flat(g_full.astype(jnp.bfloat16), dp_axes)
        return ((g.astype(jnp.float32) / D).astype(g_full.dtype),)

    gather.defvjp(fwd, bwd)
    return gather


def gather_fp(w_chunk: jax.Array, dp_axes: tuple[str, ...]) -> jax.Array:
    """Plain differentiable FSDP gather: backward is a full-precision
    reduce-scatter *sum*.  Used for small (non-LoCo) tensors; callers divide
    the resulting grads by D to get the mean (see steps.py)."""
    return _make_gather_fp(tuple(dp_axes))(w_chunk)


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def _sum_grads_over_model(x, axes):
    return x


def _sgm_fwd(x, axes):
    return x, None


def _sgm_bwd(axes, _res, g):
    return (jax.lax.psum(g, axes),)


_sum_grads_over_model.defvjp(_sgm_fwd, _sgm_bwd)


def replicated_grad_psum(x: jax.Array, tp_axis: str = "model") -> jax.Array:
    """Identity forward; backward psums the cotangent over the TP axis.

    Wrap every weight that is *replicated* across the tensor-parallel axis
    (kv projections when kv_heads < TP, norm scales, ...) so each dp node's
    local gradient is the true full gradient before LoCo sees it.
    """
    return _sum_grads_over_model(x, tp_axis)
