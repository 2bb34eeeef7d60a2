"""One run of one cell: set-up, the timed window, the trace, the check.

The system under test is the LoCo trainer's jitted train step
(``repro.launch.steps.make_train_step``), built from the cell's trainer
flags through ``repro.launch.train.build_args``/``make_run`` and the mesh
of ``repro.launch.mesh.make_local_mesh``.  Everything else is the
benchmark's own: the weights and batches of the seed, the reading of the
device trace, and the plain reference that decides ``correct``.

Set-up builds the step's state from the seed, compiles the step ahead of
time, and drives it through its first ``reference.STEPS`` steps on
distinct batches of the ring; what those steps produce (the losses, the
first gradient as Adam's first moment holds it, the parameters' change)
is read then and compared with the reference once the window has closed.
The same compiled step and state then run the window.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from chipbench import check, data, model, reference
from chipbench.spec import Cell, trainer_argv

TRACE_STEPS = 3       # window steps under the profiler in a --trace 1 run

# reference-tree leaf -> (program param group, param name)
_GROUP = {"tok": ("embed", "tok"), "head": ("final", "head"),
          "norm_f": ("final", "norm_f")}


def program_leaf(name: str) -> tuple[str, str]:
    if name.startswith("layers/"):
        return "block", name.split("/", 1)[1]
    return _GROUP[name]


class NoChip(SystemExit):
    pass


def require_chips(n: int) -> list:
    """The accelerator devices a cell may use; never falls back to the CPU."""
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"no accelerator: {e}") from None
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform} devices")
    if len(devs) < n:
        raise NoChip(f"the cell needs {n} chips, JAX found {len(devs)}")
    return devs[:n]


def check_arch(cfg, dims: model.Dims) -> None:
    """Refuse a configuration file that does not state what the program
    runs (the reference is built from the file, the program from the
    registry arch and its overrides)."""
    want = dict(d_model=dims.d, n_heads=dims.heads, n_kv_heads=dims.kv_heads,
                hd=dims.hd, d_ff=dims.ff, vocab=dims.vocab,
                n_layers=dims.layers, tied_embeddings=dims.tied,
                rope_theta=dims.rope_theta, family="dense", mlp="swiglu",
                norm="rmsnorm", qk_norm=False, attn_softcap=None,
                final_softcap=None, parallel_block=False)
    have = {k: getattr(cfg, k) for k in want}
    have["window"] = cfg.window if cfg.attn_kind == "swa" else None
    want["window"] = dims.window
    for k, scale in (("emb_scale", dims.scale_emb),
                     ("residual_scale", dims.residual_scale),
                     ("logit_scale", dims.logit_scale)):
        have[k] = round(getattr(cfg, k) or 1.0, 12)
        want[k] = round(scale, 12)
    bad = {k: (have[k], want[k]) for k in want if have[k] != want[k]}
    if bad or cfg.attn_kind not in ("full", "swa"):
        raise SystemExit(f"{cfg.name}: program arch differs from the "
                         f"configuration file (program, file): {bad}")


@dataclasses.dataclass
class Built:
    """The cell's system under test, built and fed."""

    cell: Cell
    dims: model.Dims
    mesh: jax.sharding.Mesh
    bundle: object
    step: object           # the compiled step (AOT)
    hlo: str
    b1: float


def build(cell: Cell) -> Built:
    from repro.configs.base import ShapeConfig, get_arch
    from repro.launch.mesh import make_local_mesh
    from repro.launch.steps import make_train_step
    from repro.launch.train import build_args, make_run

    dims = model.Dims.from_config(cell.config)
    cfg = dataclasses.replace(get_arch(cell.config["arch"]),
                              **cell.config.get("overrides", {}))
    check_arch(cfg, dims)
    run = make_run(build_args(trainer_argv(cell)))
    opt = cell.traffic["optimizer"]
    if (run.optimizer, run.lr, run.weight_decay, run.clip_norm,
            run.schedule, run.warmup_steps) != (
            opt["name"], opt["lr"], opt["weight_decay"], opt["clip_norm"],
            opt["schedule"], opt["warmup"]):
        raise SystemExit(f"{cell.name}: trainer flags and the traffic file's "
                         "optimizer disagree")
    mesh = make_local_mesh(dp=cell.dp, tp=cell.tp)
    shape = ShapeConfig("bench", cell.seq_len, cell.global_batch, "train")
    bundle = make_train_step(cfg, run, mesh, shape)
    step = bundle.fn.lower(*bundle.input_shapes).compile()
    return Built(cell=cell, dims=dims, mesh=mesh, bundle=bundle, step=step,
                 hlo=step.as_text(), b1=opt["b1"])


# ---------------------------------------------------------------------------
# the program's state, made from the benchmark's weights
# ---------------------------------------------------------------------------

def to_program(w: dict, chunk_shapes: dict) -> dict:
    """Logical weight tree -> the program's flat padded master chunks."""
    out = {g: {} for g in chunk_shapes}
    items = reference._flat_items(w)
    for name, x in items.items():
        g, p = program_leaf(name)
        shp = chunk_shapes[g][p].shape
        lead = x.shape[:1] if len(shp) == 3 else ()
        flat = x.reshape(*lead, -1)
        flat = jnp.pad(flat, [(0, 0)] * len(lead)
                       + [(0, shp[-1] - flat.shape[-1])])
        out[g][p] = flat.reshape(shp)
    return out


def from_program(tree: dict, names: list) -> dict:
    return {n: tree[program_leaf(n)[0]][program_leaf(n)[1]] for n in names}


def program_norms(tree: dict, names: list) -> dict:
    """Per-leaf norms of a program-layout tree (one per layer if stacked)."""
    out = {}
    for n, x in from_program(tree, names).items():
        axes = tuple(range(1, x.ndim)) if x.ndim == 3 else None
        out[n] = jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)), axis=axes))
    return out


def init_state(b: Built, key):
    cshapes, sshapes, oshapes = b.bundle.input_shapes[:3]
    shard = lambda t: jax.tree.map(lambda s: s.sharding, t)

    @functools.partial(jax.jit,
                       out_shardings=(shard(cshapes), shard(sshapes),
                                      shard(oshapes)))
    def init(key):
        chunks = to_program(model.make_weights(key, b.dims), cshapes)
        zeros = lambda t: jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), t)
        return chunks, zeros(sshapes), zeros(oshapes)

    return init(key)


def make_batches(b: Built, key) -> tuple[jax.Array, list]:
    """The ring on the host (for the reference) and as the step's batches."""
    c = b.cell
    bshape = b.bundle.input_shapes[4]["tokens"]

    @functools.partial(jax.jit, out_shardings=tuple(
        bshape.sharding for _ in range(c.traffic["ring"])))
    def ring(key):
        r = data.make_ring(key, b.dims.vocab, c.seq_len, c.global_batch,
                           c.traffic["ring"])
        return tuple(r[i] for i in range(r.shape[0]))

    parts = ring(jax.random.fold_in(key, 1))
    return np.stack(jax.device_get(parts)), [{"tokens": t} for t in parts]


# ---------------------------------------------------------------------------
# faults the comparison must catch (tests and calibration only)
# ---------------------------------------------------------------------------

def _copy(tree):
    return jax.tree.map(lambda a: a.copy(), tree)


def faulty(step, fault: str | None):
    """The compiled step with a fault planted underneath it: ``unchanged``
    (the step hands back the state it was given) or ``half`` (the second
    half of each batch repeats the first).  The exchange between chips is
    left out by the tests' own patch of the program and, for calibration,
    in the reference (``reference.Setting.fault``)."""
    if fault is None:
        return step
    if fault == "unchanged":
        def f(c, s, o, i, bt):
            out = step(_copy(c), _copy(s), _copy(o), i, bt)
            return c, s, o, out[3]
        return f
    if fault == "half":
        def f(c, s, o, i, bt):
            t = np.asarray(jax.device_get(bt["tokens"]))
            h = t.shape[0] // 2
            t2 = jax.device_put(np.concatenate([t[:h], t[:h]]),
                                bt["tokens"].sharding)
            return step(c, s, o, i, {"tokens": t2})
        return f
    raise ValueError(fault)


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def device_info(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def peak_bytes(devices) -> int | None:
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", -1))
             for d in devices]
    return max(peaks) if max(peaks) >= 0 else None


def setup(cell: Cell, cache: bool = True) -> Built:
    """The persistent compile cache (inside the checkout, or where
    ``JAX_COMPILATION_CACHE_DIR`` says), then the cell's compiled step."""
    if cache:
        from repro.launch.train import use_compile_cache

        use_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return build(cell)


def first_steps(b: Built, seed: int, fault: str | None = None):
    """Make the seed's state and batches and drive the compiled step
    through its first ``reference.STEPS`` steps.

    Returns ``(readings, state, batches, host_ring)``: what those steps
    produced (``check.gaps`` reads it), and what the window goes on with.
    """
    key = data.seed_key(seed)
    names = model.leaf_names(b.dims)
    host_ring, batches = make_batches(b, key)
    c, s, o = init_state(b, key)
    step = faulty(b.step, fault)

    @jax.jit
    def m_norms(m):
        return {n: v / (1.0 - b.b1) for n, v in program_norms(m, names).items()}

    @jax.jit
    def change_norms(chunks, key):
        w0 = to_program(model.make_weights(key, b.dims), chunks)
        return program_norms(jax.tree.map(jnp.subtract, chunks, w0), names)

    losses, g0 = [], None
    for i in range(reference.STEPS):
        c, s, o, met = step(c, s, o, step_index(b, i), batches[i])
        losses.append(met["loss"])
        if i == 0:
            g0 = m_norms(o[0])
    readings = {"losses": [float(x) for x in losses],
                "grad": jax.device_get(g0),
                "change": jax.device_get(change_norms(c, key))}
    return readings, (c, s, o), batches, host_ring


def step_index(b: Built, i: int) -> jax.Array:
    return jax.device_put(np.int32(i), NamedSharding(b.mesh, P()))


def follow_reference(b: Built, seed: int, host_ring, precision: str = "f32",
                     fault: str | None = None) -> dict:
    """The plain reference's readings of the same seed (see reference.py)."""
    cell = b.cell
    setting = reference.Setting(
        dims=b.dims, wire=reference.Wire.from_traffic(cell.traffic),
        ranks=cell.dp, micro=int(cell.traffic.get("microbatch", 1)),
        opt=cell.traffic["optimizer"], precision=precision, fault=fault)
    return reference.follow(data.seed_key(seed), host_ring, setting,
                            list(b.mesh.devices.flat))


def run(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
        fault: str | None = None, cache: bool = True,
        log=lambda *a: print(*a, file=sys.stderr, flush=True)) -> dict:
    """Set up, time the window, check; returns the result object."""
    b = setup(cell, cache)
    mesh_devs = list(b.mesh.devices.flat)
    readings, (c, s, o), batches, host_ring = first_steps(b, seed, fault)
    step = faulty(b.step, fault)

    # the window: whole steps, one in flight behind the one dispatched
    K = len(batches)
    i = reference.STEPS
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    losses, pending, n = [], None, 0
    t_w0 = time.perf_counter()
    setup_s = time.time() - t_start
    while True:
        if trace and n == 0:
            jax.profiler.start_trace(trace_dir)
        with jax.profiler.TraceAnnotation("bench/batch"):
            bt, si = batches[i % K], step_index(b, i)
        with jax.profiler.TraceAnnotation("bench/dispatch"):
            c, s, o, met = step(c, s, o, si, bt)
        with jax.profiler.TraceAnnotation("bench/wait"):
            if pending is not None:
                pending.block_until_ready()
        losses.append(met["loss"])
        pending = met["loss"]
        n += 1
        i += 1
        if trace and n == TRACE_STEPS:
            pending.block_until_ready()
            jax.profiler.stop_trace()
        if time.perf_counter() - t_w0 >= seconds and n >= (
                TRACE_STEPS if trace else 1):
            break
    pending.block_until_ready()
    window_s = time.perf_counter() - t_w0
    host_losses = np.array(jax.device_get(losses), dtype=np.float64)
    failed = int(np.sum(~np.isfinite(host_losses)))
    peak = peak_bytes(mesh_devs)
    del c, s, o, met, batches, pending, losses, step
    b.step = None
    gc.collect()

    # the reference, once the program's state is freed
    t_ref = time.perf_counter()
    ref = follow_reference(b, seed, host_ring)
    ref_s = time.perf_counter() - t_ref
    numbers = check.compare(readings, ref, cell.limits)
    correct = failed == 0 and check.passed(numbers)

    dev = device_info(mesh_devs)
    dev["memory_peak_bytes"] = peak
    result = {"correct": correct, "attempted": n, "failed": failed}
    if not trace:
        from chipbench import flops, peaks
        tps = n * cell.tokens_per_step / window_s
        pk = peaks.lookup(dev["kind"])
        metrics = {
            "tokens_per_s": (tps, "tokens/s"),
            "mfu": (100.0 * tps * flops.per_token(b.dims, cell.seq_len)
                    / (len(mesh_devs) * pk["bf16_flops"]), "%"),
            "peak_hbm_gb": (peak / 1e9 if peak is not None else None, "GB"),
            "setup_s": (setup_s, "s"),
        }
        result["metrics"] = {k: {"value": v, "unit": u}
                             for k, (v, u) in metrics.items() if v is not None}
    else:
        from chipbench import trace as TR
        from chipbench.metrics import read_all
        red = TR.reduce_dir(trace_dir, b.hlo)
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = dict(cell=cell, dims=b.dims, trace=red, steps=TRACE_STEPS,
                   hlo=b.hlo, device=dev)
        result["metrics"] = read_all(ctx, cell.name)
        dev["busy_s"] = red.busy_s
        dev["window_s"] = red.window_s
        result["breakdown"] = red.breakdown()
    result["device"] = dev
    log(f"{cell.name} seed={seed}: {n} steps in {window_s!r} s, "
        f"setup {setup_s!r} s, reference {ref_s!r} s, "
        f"losses {readings['losses']} (reference {ref['losses']})")
    for line in check.lines(numbers):
        log(line)
    result["checks"] = check.as_json(numbers)
    return result
