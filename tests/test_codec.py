"""Codec registry: wire shapes == telemetry == actual arrays, fast-path
dispatch, per-bucket use_kernels, stochastic-rounding key threading.

The ISSUE-2 acceptance properties live here:

* every registered Pallas fast path matches its codec oracle in
  interpret=True mode (CPU harness);
* with a uniform policy, the kernel-dispatched bucketed path is bit-exact
  with the jnp path for loco/4-bit (extends the PR-1 exactness property);
* ``use_kernels`` resolves per-bucket through SyncPolicy rules, exercised
  end-to-end via ``launch/train.py --policy``;
* the packed onebit payload byte-matches the telemetry prediction;
* ``stochastic_rounding`` either receives a PRNG key or fails loudly
  (regression: it used to be silently dropped).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.core import codec as C
from repro.core import policy as POL
from repro.core import quantizer as Q
from repro.core.comm import all_gather_flat, dist_sync, dist_sync_buckets
from repro.core.hijack import gather_with_sync
from repro.core.loco import (SyncConfig, init_state, local_compress, sim_init,
                             sim_sync, state_dtype)
from repro.core.quantizer import QuantConfig
from repro.telemetry import wire as W

BLOCK = QuantConfig(mode="block")


def _f8_close(a, b):
    """Equal up to one f8_e4m3 quantum (rounding-tie tolerance, see
    tests/test_kernels.py for the rationale)."""
    a = np.asarray(a.astype(jnp.float32))
    b = np.asarray(b.astype(jnp.float32))
    de = np.abs(a - b)
    quantum = np.maximum(np.maximum(np.abs(a), np.abs(b)) / 8.0, 2.0**-9)
    assert (de <= quantum + 1e-12).all()
    assert (de != 0).mean() < 5e-3


# ---------------------------------------------------------------------------
# registry + wire shapes == telemetry == actual encode outputs
# ---------------------------------------------------------------------------


def test_registry_covers_wire_strategies():
    for s in ("loco", "ef", "naive4", "onebit"):
        assert C.get_codec(SyncConfig(strategy=s)).strategy == s
    for s in ("fp", "ef21"):
        with pytest.raises(ValueError, match="no wire codec"):
            C.get_codec(SyncConfig(strategy=s))


CFGS = [
    SyncConfig(strategy="loco", quant=QuantConfig(bits=4, mode="block")),
    SyncConfig(strategy="loco", quant=QuantConfig(bits=8, mode="block")),
    SyncConfig(strategy="loco", quant=QuantConfig(bits=4, mode="fixed",
                                                  scale=2.0**10)),
    SyncConfig(strategy="ef", quant=QuantConfig(bits=8, mode="block")),
    SyncConfig(strategy="naive4", quant=QuantConfig(bits=4, mode="block")),
    SyncConfig(strategy="naive4", quant=QuantConfig(bits=8, mode="tensor")),
    SyncConfig(strategy="loco", quant=QuantConfig(bits=4, mode="tensor")),
    SyncConfig(strategy="onebit"),
]


def test_tensor_mode_scale_is_gather_leaf():
    """Tensor-mode scales are per-node dynamic, so the codec must declare
    them ``gather`` (all-gathered per peer) — a ``none`` leaf would make
    every receiver decode with its *local* scale (the old hierarchical
    broadcast bug)."""
    cfg = SyncConfig(strategy="loco", quant=QuantConfig(mode="tensor"))
    shapes = C.get_codec(cfg).wire_shapes(1024)
    assert shapes["scales"].comm == "gather"
    # fixed mode stays static: the scale is a config constant
    cfg_fixed = SyncConfig(strategy="loco", quant=QuantConfig(mode="fixed"))
    assert C.get_codec(cfg_fixed).wire_shapes(1024)["scales"].comm == "none"


@pytest.mark.parametrize("cfg", CFGS, ids=lambda c: f"{c.strategy}-"
                         f"{c.quant.bits}-{c.quant.mode}")
def test_wire_shapes_match_encode_and_telemetry(cfg):
    """codec.wire_shapes == the arrays encode actually produces == the
    telemetry byte prediction (satellite: packed onebit payload included)."""
    n = 2048
    codec = C.get_codec(cfg)
    g = jax.random.normal(jax.random.PRNGKey(0), (n,)) * 1e-3
    wire, new_state = codec.encode(g, codec.init_state(n))
    shapes = codec.wire_shapes(n)
    assert set(wire) == set(shapes)
    pay_bytes = sc_bytes = 0
    for name, leaf in shapes.items():
        arr = wire[name]
        assert arr.shape == leaf.shape, (name, arr.shape, leaf.shape)
        assert arr.dtype == jnp.dtype(leaf.dtype), (name, arr.dtype)
        nbytes = arr.size * arr.dtype.itemsize
        assert nbytes == leaf.nbytes
        if name == "payload":
            pay_bytes += nbytes
        else:
            sc_bytes += nbytes
    assert W.payload_bytes(n, cfg) == pay_bytes
    assert W.scale_bytes(n, cfg, dp=1) == sc_bytes
    if codec.needs_state():
        assert new_state.dtype == state_dtype(cfg)


def test_onebit_payload_is_bit_packed():
    """Satellite: 8 signs per wire byte — the wire costs n/8 payload bytes
    (was n), and the packed bytes decode back to the exact ±scale signal."""
    n = 4096
    cfg = SyncConfig(strategy="onebit")
    assert W.payload_bytes(n, cfg) == n // 8
    codec = C.get_codec(cfg)
    g = jax.random.normal(jax.random.PRNGKey(1), (n,)) * 1e-3
    wire, _ = codec.encode(g, codec.init_state(n))
    assert wire["payload"].size * wire["payload"].dtype.itemsize == n // 8
    d = codec.decode_mean(jax.tree.map(lambda a: a[None], wire))
    scale = float(jnp.mean(jnp.abs(g)))
    np.testing.assert_allclose(
        np.asarray(d), np.where(np.asarray(g) > 0, scale, -scale), rtol=1e-6)
    # gathered scalar scale counts once per peer
    assert W.scale_bytes(n, cfg, dp=4) == 16


def test_local_compress_equals_codec_roundtrip():
    """loco.local_compress (the simulation core) is the codec round trip —
    sim == distributed by construction, pinned for every wire strategy."""
    n = 1024
    for cfg in CFGS:
        codec = C.get_codec(cfg)
        g = jax.random.normal(jax.random.PRNGKey(2), (n,)) * 1e-3
        st = codec.init_state(n)
        d1, s1 = local_compress(g, st, cfg)
        wire, s2 = codec.encode(g, st)
        d2 = codec.decode_mean(jax.tree.map(lambda a: a[None], wire))
        np.testing.assert_array_equal(np.asarray(d1), np.asarray(d2))
        np.testing.assert_array_equal(
            np.asarray(s1.astype(jnp.float32)), np.asarray(s2.astype(jnp.float32)))


# ---------------------------------------------------------------------------
# fast-path registry: every registered cell matches its oracle (interpret)
# ---------------------------------------------------------------------------


def _cfg_for_key(key):
    strategy, bits, mode, err = key
    if strategy == "onebit":
        return SyncConfig(strategy="onebit", use_kernels=True)
    qc = QuantConfig(bits=bits, mode=mode,
                     error_codec=err if strategy == "loco" else "f8")
    return SyncConfig(strategy=strategy, quant=qc, use_kernels=True)


def test_every_registered_fastpath_matches_oracle():
    C._load_default_fastpaths()
    assert len(C.FASTPATHS) >= 7  # loco4/8, ef4/8, naive4 x2, onebit
    n, D = 4 * 512, 2
    for key, fp in sorted(C.FASTPATHS.items()):
        cfg = _cfg_for_key(key)
        assert C.fastpath_key(cfg) == key, key
        codec = C.get_codec(cfg)
        g = jax.random.normal(jax.random.PRNGKey(3), (n,)) * 1e-3
        st = codec.init_state(n)
        if codec.needs_state():  # non-trivial compensation input
            st = (jax.random.normal(jax.random.PRNGKey(4), (n,)) * 1e-4
                  ).astype(st.dtype) if st.dtype != jnp.float8_e4m3fn else (
                      jax.random.normal(jax.random.PRNGKey(4), (n,)) * 40
                  ).astype(st.dtype)
        if fp.encode is not None:
            wire_k, st_k = fp.encode(cfg, g, st)
            wire_r, st_r = codec.encode_ref(g, st)
            for name in wire_r:
                np.testing.assert_array_equal(
                    np.asarray(wire_k[name]), np.asarray(wire_r[name]),
                    err_msg=f"{key} wire[{name}]")
            if st_k.dtype == jnp.float8_e4m3fn:
                _f8_close(st_k, st_r)
            else:
                np.testing.assert_array_equal(
                    np.asarray(st_k.astype(jnp.float32)),
                    np.asarray(st_r.astype(jnp.float32)), err_msg=str(key))
        if fp.decode_mean is not None:
            wire_r, _ = codec.encode_ref(g, codec.init_state(n))
            recv = jax.tree.map(
                lambda a: jnp.stack([a] * D) if a.size > 1
                else jnp.broadcast_to(a, (D,) + a.shape), wire_r)
            out_k = fp.decode_mean(cfg, recv)
            out_r = codec.decode_mean_ref(recv)
            np.testing.assert_array_equal(np.asarray(out_k), np.asarray(out_r),
                                          err_msg=str(key))


def test_nondefault_block_size_falls_back_to_oracle():
    """The fused kernels tile at 256-element quantizer blocks; a config
    with block=128 must not dispatch them (regression: the registry key
    omits `block`, so the guard lives in fastpath_for)."""
    qc = QuantConfig(bits=4, mode="block", block=128)
    kcfg = SyncConfig(strategy="loco", quant=qc, use_kernels=True)
    assert C.fastpath_for(kcfg) is None
    n = 2048
    codec = C.get_codec(kcfg)
    g = jax.random.normal(jax.random.PRNGKey(12), (n,)) * 1e-3
    wire, _ = codec.encode(g, codec.init_state(n))
    for name, leaf in codec.wire_shapes(n).items():
        assert wire[name].shape == leaf.shape, name  # 128-block scales kept


def test_threaded_key_keeps_fastpath():
    """A PRNG key threaded with stochastic_rounding OFF (e.g. a uniform
    dist_sync_buckets key) must not silently disable the kernels."""
    kcfg = SyncConfig(strategy="loco", use_kernels=True,
                      quant=QuantConfig(bits=4, mode="block"))
    codec = C.get_codec(kcfg)
    n = 1024
    g = jax.random.normal(jax.random.PRNGKey(13), (n,)) * 1e-3
    st = codec.init_state(n)
    w0, s0 = codec.encode(g, st, key=None)
    w1, s1 = codec.encode(g, st, key=jax.random.PRNGKey(0))
    for name in w0:
        np.testing.assert_array_equal(np.asarray(w0[name]), np.asarray(w1[name]))
    np.testing.assert_array_equal(np.asarray(s0.astype(jnp.float32)),
                                  np.asarray(s1.astype(jnp.float32)))


def test_ef21_stochastic_rounding_loud_or_keyed():
    """ef21 lives outside the codec registry but follows the same SR
    contract: no key -> loud failure, key -> applied."""
    cfg = dataclasses.replace(SR, strategy="ef21")
    n = 1024
    g = jax.random.normal(jax.random.PRNGKey(14), (n,))
    st = jnp.zeros((n,), jnp.bfloat16)
    with pytest.raises(ValueError, match="stochastic_rounding"):
        local_compress(g, st, cfg)
    d1, _ = local_compress(g, st, cfg, key=jax.random.PRNGKey(0))
    d2, _ = local_compress(g, st, cfg, key=jax.random.PRNGKey(1))
    assert np.abs(np.asarray(d1) - np.asarray(d2)).max() > 0


def test_unregistered_combo_falls_back_to_oracle():
    """use_kernels on a cell with no fused path (fixed mode) must not
    change results — the codec dispatch silently uses the jnp oracle."""
    qc = QuantConfig(bits=4, mode="fixed", scale=2.0**10)
    base = SyncConfig(strategy="loco", quant=qc)
    kcfg = dataclasses.replace(base, use_kernels=True)
    assert C.fastpath_for(kcfg) is None
    n = 1024
    g = jax.random.normal(jax.random.PRNGKey(5), (n,)) * 1e-3
    d1, s1 = local_compress(g, init_state(base, n), base)
    d2, s2 = local_compress(g, init_state(kcfg, n), kcfg)
    np.testing.assert_array_equal(np.asarray(d1), np.asarray(d2))
    np.testing.assert_array_equal(
        np.asarray(s1.astype(jnp.float32)), np.asarray(s2.astype(jnp.float32)))


# ---------------------------------------------------------------------------
# kernel-dispatched bucketed path == jnp monolithic path (acceptance)
# ---------------------------------------------------------------------------


def _uniform_pplan(C_, D, sizes, cfg):
    from repro.core import buckets as BK
    bs, off = [], 0
    for i, c in enumerate(sizes):
        bs.append(BK.Bucket(index=i, offset=off, chunk_elems=c,
                            seg_elems=D * c, sync=cfg))
        off += c
    return BK.ParamPlan(group="g", name="p", tensor_class="body",
                        chunklen=C_, layers=1, buckets=tuple(bs))


@pytest.mark.parametrize("strategy,bits", [("loco", 4), ("loco", 8),
                                           ("ef", 4), ("onebit", 1)])
def test_bucketed_kernel_path_bitexact_jnp(mesh22, strategy, bits):
    """Uniform use_kernels=True policy, bucketed, vs the jnp path.

    The kernel-dispatched bucketed run must equal the jnp bucketed run bit
    for bit; for the quantized codecs (block edges = quantizer blocks) it
    must *also* equal the monolithic jnp path, extending the PR-1 exactness
    property through the kernel dispatch.  (onebit's per-bucket L1 scale
    differs from the per-tensor scale, so only the first claim applies —
    same carve-out as DESIGN.md §7.)
    """
    qc = QuantConfig(bits=bits if bits in (4, 8) else 4, mode="block")
    cfg = SyncConfig(strategy=strategy, quant=qc)
    cfg_k = dataclasses.replace(cfg, use_kernels=True)
    D, sizes = 2, (512, 1024, 512)
    C_ = sum(sizes)
    n = D * C_
    plan_j = _uniform_pplan(C_, D, sizes, cfg)
    plan_k = _uniform_pplan(C_, D, sizes, cfg_k)

    def scatter_states(ns_b):
        flat = jnp.zeros((D, C_), jnp.float32)
        for b, ns in zip(plan_k.buckets, ns_b):
            flat = flat.at[:, b.offset:b.offset + b.chunk_elems].set(
                ns.astype(jnp.float32).reshape(D, b.chunk_elems))
        return flat.reshape(-1)

    def body(g):
        g_local = g.reshape(-1)
        states = tuple(
            jnp.zeros((b.seg_elems,), state_dtype(cfg)) if cfg.needs_state()
            else jnp.zeros((1,), jnp.float32) for b in plan_k.buckets)
        sh_m, _ = dist_sync(g_local, init_state(cfg, n), cfg, ("data",))
        sh_j, ns_j = dist_sync_buckets(g_local, states, plan_j, ("data",))
        sh_k, ns_k = dist_sync_buckets(g_local, states, plan_k, ("data",))
        return (sh_m[None], sh_j[None], sh_k[None],
                scatter_states(ns_j)[None], scatter_states(ns_k)[None])

    fn = jax.jit(jax.shard_map(
        body, mesh=mesh22, in_specs=(P("data"),),
        out_specs=(P("data"),) * 5, check_vma=False))
    g = jax.random.normal(jax.random.PRNGKey(0), (D, n)) * 1e-3
    sh_m, sh_j, sh_k, ns_j, ns_k = fn(g)
    # kernel-dispatched bucketed == jnp bucketed, bit for bit
    np.testing.assert_array_equal(np.asarray(sh_j), np.asarray(sh_k))
    if cfg.needs_state():
        if state_dtype(cfg) == jnp.float8_e4m3fn:
            _f8_close(jnp.asarray(ns_j), jnp.asarray(ns_k))
        else:
            np.testing.assert_array_equal(np.asarray(ns_j), np.asarray(ns_k))
    if strategy != "onebit":  # and == the monolithic jnp path (PR-1 property)
        np.testing.assert_array_equal(np.asarray(sh_m), np.asarray(sh_k))


# ---------------------------------------------------------------------------
# per-bucket use_kernels through SyncPolicy (+ end-to-end --policy)
# ---------------------------------------------------------------------------


def test_policy_kernels_flag():
    base = SyncConfig(strategy="loco", quant=BLOCK)
    pol = POL.parse_policy("body=loco4+kernels,embed=loco8,norm=fp", base)
    body = pol.resolve("b/wq", "body", 1 << 20)
    assert body.use_kernels and body.strategy == "loco" and body.quant.bits == 4
    assert not pol.resolve("e/tok", "embed", 1 << 20).use_kernels
    assert pol.resolve("b/n1", "norm", 1 << 20).strategy == "fp"
    # +nokernels overrides a kernels-on run default per class
    kbase = dataclasses.replace(base, use_kernels=True)
    pol2 = POL.parse_policy("norm=loco4+nokernels", kbase)
    assert not pol2.resolve("b/n1", "norm", 1 << 20).use_kernels
    assert pol2.resolve("b/wq", "body", 1 << 20).use_kernels  # default kept
    with pytest.raises(ValueError, match="unknown preset flag"):
        POL.parse_policy("body=loco4+turbo", base)


def test_train_cli_policy_kernels_end_to_end(capsys):
    """launch/train.py --policy 'body=loco4+kernels' runs the bucketed,
    kernel-dispatched path for real (acceptance criterion)."""
    from repro.launch import train as T
    res = T.main([
        "--arch", "llama2-400m", "--reduced", "--steps", "2",
        "--seq-len", "16", "--global-batch", "4", "--dp", "2", "--tp", "1",
        "--sync", "loco", "--bucket-mb", "0.0625",
        "--policy", "body=loco4+kernels,min=4096", "--log-every", "1"])
    assert np.isfinite(res.loss)
    assert sorted(res.losses) == [0, 1]
    out = capsys.readouterr().out
    assert "wire/step/device" in out  # plan report printed


# ---------------------------------------------------------------------------
# stochastic rounding: threaded key or loud failure (satellite regression)
# ---------------------------------------------------------------------------

SR = SyncConfig(strategy="loco",
                quant=QuantConfig(mode="block", stochastic_rounding=True))


def test_stochastic_rounding_requires_key():
    """dist_sync/local_compress used to silently call Q.compress(key=None);
    now the codec fails loudly when no key reaches the encode path."""
    n = 1024
    g = jax.random.normal(jax.random.PRNGKey(6), (n,))
    with pytest.raises(ValueError, match="stochastic_rounding"):
        local_compress(g, init_state(SR, n), SR)
    # hijack path: rejected at gather-build time (no key plumbing exists)
    with pytest.raises(ValueError, match="stochastic_rounding"):
        gather_with_sync(jnp.zeros((n,), jnp.bfloat16),
                         jnp.zeros((n,), jnp.float8_e4m3fn), SR, ("data",))
    # step builder: rejected at config time before any tracing
    from repro.core.flatparam import MeshTopo
    from repro.launch.steps import _validate_sync_configs, RunConfig
    topo = MeshTopo(dp_axes=("data",), tp_axis="model", dp=2, tp=2)
    with pytest.raises(ValueError, match="stochastic_rounding"):
        _validate_sync_configs(RunConfig(sync=SR), None, topo)


def test_stochastic_rounding_key_threads_and_varies():
    n = 1024
    g = jax.random.normal(jax.random.PRNGKey(7), (n,))  # O(1) values round
    st = init_state(SR, n)
    d1, _ = local_compress(g, st, SR, key=jax.random.PRNGKey(0))
    d2, _ = local_compress(g, st, SR, key=jax.random.PRNGKey(1))
    assert np.abs(np.asarray(d1) - np.asarray(d2)).max() > 0
    # sim_sync derives fresh per-step keys when none is passed
    gn = jnp.stack([g, -g])
    s0 = sim_init(SR, 2, n)
    ga, _ = sim_sync(gn, s0, jnp.int32(1), SR)
    gb, _ = sim_sync(gn, s0, jnp.int32(2), SR)
    assert np.abs(np.asarray(ga) - np.asarray(gb)).max() > 0
    # and explicit keys are reproducible
    gc1, _ = sim_sync(gn, s0, jnp.int32(1), SR, key=jax.random.PRNGKey(9))
    gc2, _ = sim_sync(gn, s0, jnp.int32(1), SR, key=jax.random.PRNGKey(9))
    np.testing.assert_array_equal(np.asarray(gc1), np.asarray(gc2))


def test_dist_sync_threads_sr_key(mesh22):
    """The distributed path accepts and applies a rounding key (the old
    code path dropped it on the floor)."""
    n = 2 * 512

    def body(g, k):
        sh, _ = dist_sync(g.reshape(-1), jnp.zeros((1,), jnp.float32),
                          dataclasses.replace(SR, strategy="naive4"),
                          ("data",), key=k[0])
        return all_gather_flat(sh, ("data",))[None]

    fn = jax.jit(jax.shard_map(
        body, mesh=mesh22, in_specs=(P("data"), P(None)),
        out_specs=P(None), check_vma=False))
    g = jax.random.normal(jax.random.PRNGKey(8), (2, n))
    r1 = fn(g, jax.random.PRNGKey(0)[None])
    r2 = fn(g, jax.random.PRNGKey(1)[None])
    assert np.abs(np.asarray(r1) - np.asarray(r2)).max() > 0


# ---------------------------------------------------------------------------
# topk ragged codec (ISSUE 8): wire form, error feedback, byte accounting
# ---------------------------------------------------------------------------


def test_topk_wire_form_and_error_feedback():
    """The topk wire is a capacity-padded ragged leaf pair + u32 counts:
    shapes match the telemetry contract, counts never exceed k, dead slots
    are zero on the wire, and the untransmitted mass lands in the LoCo
    error state (beta-weighted, up to f8 requantization)."""
    cfg = SyncConfig(strategy="topk", topk_frac=0.05,
                     quant=QuantConfig(mode="block"))
    codec = C.get_codec(cfg)
    n = 4 * 512
    g = jax.random.normal(jax.random.PRNGKey(3), (n,)) * 1e-3
    wire, st = codec.encode(g, codec.init_state(n))
    shapes = codec.wire_shapes(n)
    for name, leaf in shapes.items():
        assert wire[name].shape == leaf.shape, name
        assert wire[name].dtype == leaf.dtype, name
    assert shapes["idx"].count_of == "cnt" and shapes["val"].count_of == "cnt"
    k, cap = C.topk_k(cfg), C.topk_cap(cfg)
    assert 0 < k <= cap <= C.TOPK_SEL and cap % 4 == 0
    cnt = np.asarray(wire["cnt"])
    assert (cnt <= k).all()
    val = np.asarray(wire["val"].astype(jnp.float32)).reshape(-1, cap)
    for b, c in enumerate(cnt):
        assert (val[b, int(c):] == 0).all(), b
    # single-sender decode == the encoder's own reconstruction d; with the
    # default beta=0.5 the error state records beta*(h - d) (h = g here:
    # zero initial error), so d + decode(e)/beta rebuilds g up to one f8 ulp
    d = codec.decode_mean({kk: v[None] for kk, v in wire.items()})
    e = np.asarray(codec.state_decode(st))
    resid = np.abs(np.asarray(d) + e / cfg.beta - np.asarray(g))
    assert resid.max() < 0.1 * np.abs(np.asarray(g)).max()
    # sparsity actually happened: at 5% the reconstruction is mostly zeros
    assert (np.asarray(d) != 0).mean() < 0.1


def test_topk_byte_accounting():
    """payload/scale/effective byte split for the ragged wire: capacity
    bytes are what pack reserves, effective bytes are what the live counts
    amortize to (u32 count + k (u16, bf16) pairs per block); topk_frac=1.0
    degenerates to dense (effective == capacity)."""
    cfg = SyncConfig(strategy="topk", topk_frac=0.05)
    n = 8 * 512
    u, cap, k = n // C.TOPK_SEL, C.topk_cap(cfg), C.topk_k(cfg)
    assert W.payload_bytes(n, cfg) == u * cap * (2 + 2)
    assert W.scale_bytes(n, cfg) == u * 4
    eff = W.effective_wire_bytes(n, cfg)
    assert eff == u * (4 + 4 * k)
    assert eff <= W.payload_bytes(n, cfg) + W.scale_bytes(n, cfg)
    full = SyncConfig(strategy="topk", topk_frac=1.0)
    assert W.effective_wire_bytes(n, full) == \
        W.payload_bytes(n, full) + W.scale_bytes(n, full)
    # dense codecs are unchanged: effective == payload + scales
    dense = SyncConfig(strategy="loco", quant=QuantConfig(mode="block"))
    assert W.effective_wire_bytes(n, dense) == \
        W.payload_bytes(n, dense) + W.scale_bytes(n, dense)
