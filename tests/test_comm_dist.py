"""Distributed collectives: dist_sync == simulation, hijack semantics,
and the codec-level two-stage (hierarchical) scheduler."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import buckets as BK
from repro.core.comm import (all_gather_flat, all_to_all_chunks, dist_sync,
                             dist_sync_buckets, psum_scatter_flat)
from repro.core.hijack import gather_fp, gather_with_sync
from repro.core.loco import (SyncConfig, SyncTier, init_state, sim_init,
                             sim_sync, sim_sync_hier, sync_schedule)
from repro.core.quantizer import QuantConfig


def _dist_sync_once(mesh, dp_axes, cfg, g_nodes, state_nodes):
    """Run dist_sync over a real mesh; returns (gathered g_hat, new states)."""
    N, n = g_nodes.shape

    def body(g, st):
        g_local = g.reshape(-1)          # (n,) this node's gradient
        st_local = st.reshape(-1)
        g_shard, new_st = dist_sync(g_local, st_local, cfg, dp_axes)
        full = all_gather_flat(g_shard, dp_axes)  # reassemble for comparison
        return full, new_st[None]

    spec_g = P(dp_axes if len(dp_axes) > 1 else dp_axes[0])
    fn = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(spec_g, spec_g),
        out_specs=(P(None), spec_g), check_vma=False))
    return fn(g_nodes, state_nodes)


@pytest.mark.parametrize("strategy", ["fp", "loco", "ef", "naive4", "topk"])
def test_dist_matches_simulation(mesh22, strategy):
    """The shard_map dist_sync reproduces the N-node simulation bit-for-bit
    (modulo fp baseline's bf16 wire)."""
    cfg = SyncConfig(strategy=strategy, quant=QuantConfig(mode="block"))
    N, n = 2, 2 * 512  # dp=2
    key = jax.random.PRNGKey(0)
    g = jax.random.normal(key, (N, n)) * 1e-3
    st_sim = sim_init(cfg, N, n)
    ghat_sim, st_sim2 = sim_sync(g, st_sim, jnp.int32(1), cfg)

    st_dist = jnp.stack([init_state(cfg, n) for _ in range(N)])
    ghat_dist, st_dist2 = _dist_sync_once(mesh22, ("data",), cfg, g, st_dist)
    # fp wire is bf16 -> absolute error up to a bf16 ulp of ~1e-3 values
    rtol, atol = (2e-3, 1e-5) if strategy == "fp" else (1e-6, 1e-9)
    np.testing.assert_allclose(np.asarray(ghat_dist), np.asarray(ghat_sim),
                               rtol=rtol, atol=atol)
    if cfg.needs_state():
        # maybe_reset not applied in dist path (runs in the train step)
        np.testing.assert_allclose(
            np.asarray(st_dist2.astype(jnp.float32)),
            np.asarray(st_sim2.astype(jnp.float32)), atol=1e-6)


def test_dist_sync_multi_axis(mesh_pod):
    """Joint ('pod','data') dp group behaves like a flat 4-node group."""
    cfg = SyncConfig(strategy="loco", quant=QuantConfig(mode="block"))
    N, n = 4, 4 * 512
    g = jax.random.normal(jax.random.PRNGKey(1), (N, n)) * 1e-3
    ghat_sim, _ = sim_sync(g, sim_init(cfg, N, n), jnp.int32(1), cfg)
    st = jnp.stack([init_state(cfg, n) for _ in range(N)])
    ghat, _ = _dist_sync_once(mesh_pod, ("pod", "data"), cfg, g, st)
    np.testing.assert_allclose(np.asarray(ghat), np.asarray(ghat_sim), atol=1e-7)


def test_all_to_all_chunks_identity(mesh22):
    """Row i of the exchange lands on peer i, in rank order."""
    def body(x):
        r = jax.lax.axis_index("data")
        # (2, 2): my payload row for each peer
        rows = jnp.stack([r * 10 + 0 * jnp.arange(2), r * 10 + jnp.arange(2)]).astype(jnp.int32)
        recv = all_to_all_chunks(rows, ("data",))
        return recv[None]

    fn = jax.jit(jax.shard_map(body, mesh=mesh22, in_specs=(P("data"),),
                               out_specs=P("data"), check_vma=False))
    out = fn(jnp.zeros((2, 1), jnp.int32))
    # device d receives row j = peer j's chunk-for-d
    assert out.shape == (2, 2, 2)
    host = np.asarray(out)
    assert host[0, 1, 0] == 10  # peer 1's payload row 0 as received by dev 0... row semantics
    assert host[1, 0, 1] == 1   # peer 0's row for dev 1 is [0*10+arange][1] = 1


def test_gather_fp_grad_is_mean(mesh22):
    n = 2 * 512
    x = jax.random.normal(jax.random.PRNGKey(2), (n,))

    def step(w, xx):
        def loss(w):
            return jnp.sum(gather_fp(w, ("data",)).astype(jnp.float32) * xx)
        return jax.grad(loss)(w)

    fn = jax.jit(jax.shard_map(step, mesh=mesh22, in_specs=(P("data"), P(None)),
                               out_specs=P("data"), check_vma=False))
    g = fn(jnp.zeros((n,), jnp.bfloat16), x)
    # identical local losses on both dp ranks -> mean == each local grad == x
    np.testing.assert_allclose(np.asarray(g, np.float32), np.asarray(x), atol=2e-2)


def test_hijack_state_threading(mesh22):
    """The error produced by backward #1 feeds backward #2, and the
    error-feedback bounds the *accumulated* deviation (Lemma 2): with an
    identical gradient each step, naive quantization repeats the same
    rounding error (deviation 2x), while LoCo's compensation cancels it."""
    qfix = QuantConfig(mode="fixed", scale=2.0**10, error_scale=2.0**14)
    cfg = SyncConfig(strategy="loco", quant=qfix, beta=1.0)
    cfg_naive = SyncConfig(strategy="naive4", quant=qfix)
    n = 2 * 512
    x = (jax.random.normal(jax.random.PRNGKey(3), (n,)) * 1e-3).astype(jnp.float32)

    def two_steps(w, e, xx):
        def loss(c, w, e):
            return jnp.sum(gather_with_sync(w, e, c, ("data",)).astype(jnp.float32) * xx)
        from functools import partial
        g1, e1 = jax.grad(partial(loss, cfg), argnums=(0, 1))(w, e)
        g2, _ = jax.grad(partial(loss, cfg), argnums=(0, 1))(w, e1)
        gn, _ = jax.grad(partial(loss, cfg_naive), argnums=(0, 1))(
            w, jnp.zeros((1,), jnp.float32))
        return g1, g2, gn, e1

    fn = jax.jit(jax.shard_map(
        two_steps, mesh=mesh22,
        in_specs=(P("data"), P(None), P(None)),
        out_specs=(P("data"), P("data"), P("data"), P(None)), check_vma=False))
    w = jnp.zeros((n,), jnp.bfloat16)
    e = jnp.zeros((n,), jnp.float8_e4m3fn)
    g1, g2, gn, e1 = fn(w, e, x)
    assert float(jnp.abs(e1.astype(jnp.float32)).max()) > 0
    acc_loco = jnp.abs(g1.astype(jnp.float32) + g2.astype(jnp.float32) - 2 * x).mean()
    acc_naive = jnp.abs(2 * gn.astype(jnp.float32) - 2 * x).mean()
    assert float(acc_loco) < 0.7 * float(acc_naive), (float(acc_loco), float(acc_naive))


def test_hierarchical_chunk_layout(mesh_pod):
    """hierarchical_sync delivers device (p, d) the same contiguous
    chunk r = p*Dd + d as the flat multi-axis all2all — per-rank shards line
    up slice-for-slice with the 4-node simulation, with only the bounded
    stage-2 8-bit requantization error on top."""
    qf = QuantConfig(mode="block")
    N, n = 4, 4 * 512
    c = n // N
    g = jax.random.normal(jax.random.PRNGKey(11), (N, n)) * 1e-3
    spec = P(("pod", "data"))

    def make_body(cfg):
        def body(gg, st):
            g_shard, _ = dist_sync(gg.reshape(-1), st.reshape(-1), cfg,
                                   ("pod", "data"))
            return g_shard[None]
        return body

    shards = {}
    for name, hier in (("flat", False), ("hier", True)):
        cfg = SyncConfig(strategy="loco", quant=qf, hierarchical=hier)
        st = jnp.stack([init_state(cfg, n) for _ in range(N)])
        fn = jax.jit(jax.shard_map(make_body(cfg), mesh=mesh_pod,
                                   in_specs=(spec, spec), out_specs=spec,
                                   check_vma=False))
        shards[name] = np.asarray(fn(g, st))  # (N, c): row r = rank r's shard

    cfg_ref = SyncConfig(strategy="loco", quant=qf)
    ghat_sim, _ = sim_sync(g, sim_init(cfg_ref, N, n), jnp.int32(1), cfg_ref)
    ghat_sim = np.asarray(ghat_sim)
    scale = np.abs(ghat_sim).max()
    for r in range(N):
        # flat path: rank r's shard IS the contiguous chunk r (bit-exact
        # vs simulation); hierarchical: same layout, bounded dequant error.
        np.testing.assert_allclose(shards["flat"][r], ghat_sim[r * c:(r + 1) * c],
                                   atol=1e-7)
        err = np.abs(shards["hier"][r] - ghat_sim[r * c:(r + 1) * c]).max()
        assert err < 0.02 * scale, (r, err, scale)


def test_hierarchical_matches_flat(mesh_pod):
    """Two-stage (intra-pod 4-bit + inter-pod 8-bit) exchange ~= flat all2all
    (stage-2 requantization adds <1% relative deviation)."""
    qf = QuantConfig(mode="block")
    flat = SyncConfig(strategy="loco", quant=qf)
    hier = SyncConfig(strategy="loco", quant=qf, hierarchical=True)
    N, n = 4, 4 * 512
    g = jax.random.normal(jax.random.PRNGKey(7), (N, n)) * 1e-3
    st = jnp.stack([init_state(flat, n) for _ in range(N)])
    gf, stf = _dist_sync_once(mesh_pod, ("pod", "data"), flat, g, st)
    gh, sth = _dist_sync_once(mesh_pod, ("pod", "data"), hier, g, st)
    rel = float(jnp.abs(gh - gf).max() / jnp.abs(gf).max())
    assert rel < 0.02, rel
    # error states identical (feedback covers stage 1 only, same in both)
    np.testing.assert_array_equal(
        np.asarray(stf.astype(jnp.float32)), np.asarray(sth.astype(jnp.float32)))


# ---------------------------------------------------------------------------
# codec-level two-stage scheduler (ISSUE 3 tentpole)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["block", "fixed", "tensor"])
@pytest.mark.parametrize("strategy", ["loco", "ef", "naive4", "onebit", "topk"])
def test_hierarchical_matches_simulation(mesh_pod, strategy, mode):
    """Hierarchical dist_sync is BIT-EXACT with sim_sync_hier for every
    registered strategy x quant mode: both run the same codec round trips
    (stage 1 = the bucket codec intra-pod, stage 2 = the stateless 8-bit
    block codec on the pod means), so sim == dist by construction — the
    acceptance property of the two-stage rebuild."""
    cfg = SyncConfig(strategy=strategy,
                     quant=QuantConfig(mode=mode, scale=2.0**10),
                     hierarchical=True)
    N, n = 4, 4 * 512
    g = jax.random.normal(jax.random.PRNGKey(5), (N, n)) * 1e-3
    ghat_sim, st_sim = sim_sync_hier(g, sim_init(cfg, N, n), jnp.int32(1),
                                     cfg, pods=2)
    st = jnp.stack([init_state(cfg, n) for _ in range(N)])
    ghat, st2 = _dist_sync_once(mesh_pod, ("pod", "data"), cfg, g, st)
    np.testing.assert_array_equal(np.asarray(ghat), np.asarray(ghat_sim))
    if cfg.needs_state():
        # step=1 never fires maybe_reset (reset_every=512), so sim and
        # dist states are directly comparable
        np.testing.assert_array_equal(
            np.asarray(st2.astype(jnp.float32)),
            np.asarray(st_sim.astype(jnp.float32)))


def test_hierarchical_tensor_scale_regression(mesh_pod):
    """Regression (ISSUE 3 satellite): the pre-rebuild stage 1 broadcast the
    *local* scale over the pod (`jnp.broadcast_to(scales, (Dd, 1))`) for
    every non-block mode, so a peer's payload was dequantized with the
    wrong scale whenever per-node scales differ.  Tensor mode makes the
    scales dynamic per node: give the nodes wildly different magnitudes and
    require dist == sim bit-exact AND a sane mean (the local-scale decode
    is off by the magnitude ratio, ~64x here)."""
    cfg = SyncConfig(strategy="naive4",
                     quant=QuantConfig(bits=8, mode="tensor"),
                     hierarchical=True)
    N, n = 4, 4 * 512
    mags = jnp.array([1.0, 64.0, 1.0 / 64.0, 8.0])[:, None]
    g = jax.random.normal(jax.random.PRNGKey(9), (N, n)) * mags
    ghat_sim, _ = sim_sync_hier(g, sim_init(cfg, N, n), jnp.int32(1), cfg,
                                pods=2)
    st = jnp.stack([init_state(cfg, n) for _ in range(N)])
    ghat, _ = _dist_sync_once(mesh_pod, ("pod", "data"), cfg, g, st)
    np.testing.assert_array_equal(np.asarray(ghat), np.asarray(ghat_sim))
    # and the decoded mean tracks the true mean (peer scales were honored)
    true_mean = np.asarray(jnp.mean(g, axis=0))
    err = np.abs(np.asarray(ghat) - true_mean).max()
    assert err < 0.05 * np.abs(true_mean).max(), err


def test_hierarchical_stage2_config(mesh_pod):
    """A configured stage-2 codec is honored: 4-bit stage 2 moves half the
    DCN bytes but adds requantization error vs the 8-bit default."""
    qf = QuantConfig(mode="block")
    base = SyncConfig(strategy="loco", quant=qf, hierarchical=True)
    s2_4bit = SyncConfig(strategy="naive4",
                         quant=dataclasses.replace(qf, bits=4))
    hier4 = dataclasses.replace(base, stage2=s2_4bit)
    N, n = 4, 4 * 512
    g = jax.random.normal(jax.random.PRNGKey(13), (N, n)) * 1e-3
    for cfg in (base, hier4):
        ghat_sim, _ = sim_sync_hier(g, sim_init(cfg, N, n), jnp.int32(1),
                                    cfg, pods=2)
        st = jnp.stack([init_state(cfg, n) for _ in range(N)])
        ghat, _ = _dist_sync_once(mesh_pod, ("pod", "data"), cfg, g, st)
        np.testing.assert_array_equal(np.asarray(ghat), np.asarray(ghat_sim))
    flat = dataclasses.replace(base, hierarchical=False)
    st = jnp.stack([init_state(flat, n) for _ in range(N)])
    gf, _ = _dist_sync_once(mesh_pod, ("pod", "data"), flat, g, st)
    ghat8, _ = _dist_sync_once(mesh_pod, ("pod", "data"), base, g, st)
    ghat4, _ = _dist_sync_once(mesh_pod, ("pod", "data"), hier4, g, st)
    err8 = float(jnp.abs(ghat8 - gf).max())
    err4 = float(jnp.abs(ghat4 - gf).max())
    assert err4 > err8 > 0.0, (err4, err8)
    assert err4 < 0.1 * float(jnp.abs(gf).max()), err4


def test_hierarchical_rejects_unsupported():
    """Silent flat fallback is gone: 1-axis meshes and codec-less
    strategies raise loudly (satellite regression)."""
    from repro.core.comm import hierarchical_sync
    g = jnp.zeros((1024,))
    st = jnp.zeros((1,))
    with pytest.raises(ValueError, match=r"\(pod, data\) mesh"):
        hierarchical_sync(g, st, SyncConfig(strategy="loco",
                                            hierarchical=True), ("data",))
    with pytest.raises(ValueError, match="no.*codec|registered wire codec"):
        hierarchical_sync(g, st, SyncConfig(strategy="ef21",
                                            hierarchical=True),
                          ("pod", "data"))
    with pytest.raises(ValueError, match="registered wire codec"):
        sim_sync_hier(jnp.zeros((4, 2048)), jnp.zeros((4, 1)), jnp.int32(0),
                      SyncConfig(strategy="fp", hierarchical=True), pods=2)
    with pytest.raises(ValueError, match="stateless"):
        cfg = SyncConfig(strategy="loco", hierarchical=True,
                         stage2=SyncConfig(strategy="onebit"))
        sim_sync_hier(jnp.zeros((4, 2048)),
                      jnp.zeros((4, 2048), jnp.float8_e4m3fn),
                      jnp.int32(0), cfg, pods=2)


def test_bucketed_hierarchical_mixed_plan(mesh_pod):
    """dist_sync_buckets honors `hierarchical` per bucket: a plan mixing a
    two-stage loco bucket with a flat naive4 bucket reproduces, bucket by
    bucket, the matching simulation forms."""
    qf = QuantConfig(mode="block")
    hier = SyncConfig(strategy="loco", quant=qf, hierarchical=True)
    flat = SyncConfig(strategy="naive4", quant=qf)
    N = 4
    sizes = (512, 512)
    C = sum(sizes)
    n = N * C
    buckets, off = [], 0
    for i, (c, s) in enumerate(zip(sizes, (hier, flat))):
        buckets.append(BK.Bucket(index=i, offset=off, chunk_elems=c,
                                 seg_elems=N * c, sync=s))
        off += c
    pplan = BK.ParamPlan(group="g", name="p", tensor_class="body",
                         chunklen=C, layers=1, buckets=tuple(buckets))

    def body(g):
        states = (init_state(hier, N * sizes[0])[None].reshape(-1),
                  init_state(flat, N * sizes[1]))
        sh, _ = dist_sync_buckets(g.reshape(-1), states, pplan,
                                  ("pod", "data"))
        return all_gather_flat(sh, ("pod", "data"))[None]

    spec = P(("pod", "data"))
    fn = jax.jit(jax.shard_map(body, mesh=mesh_pod, in_specs=(spec,),
                               out_specs=P(None), check_vma=False))
    g = jax.random.normal(jax.random.PRNGKey(21), (N, n)) * 1e-3
    got = np.asarray(fn(g)[0])  # (n,) averaged gradient, chunk-major

    # references: per-bucket sim over the column-sliced segments
    gm = np.asarray(g).reshape(N, N, C)
    want = np.zeros((N, C), np.float32)
    for b, sim_fn in zip(pplan.buckets, (
            lambda gb: sim_sync_hier(gb, sim_init(hier, N, gb.shape[1]),
                                     jnp.int32(1), hier, pods=2)[0],
            lambda gb: sim_sync(gb, sim_init(flat, N, gb.shape[1]),
                                jnp.int32(1), flat)[0])):
        seg = jnp.asarray(gm[:, :, b.offset:b.offset + b.chunk_elems]
                          .reshape(N, -1))
        want[:, b.offset:b.offset + b.chunk_elems] = (
            np.asarray(sim_fn(seg)).reshape(N, b.chunk_elems))
    np.testing.assert_array_equal(got, want.reshape(-1))


# ---------------------------------------------------------------------------
# two-stage wire telemetry (acceptance: prediction == actual array bytes)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("cfg", [
    SyncConfig(strategy="loco", quant=QuantConfig(bits=4, mode="block"),
               hierarchical=True),
    SyncConfig(strategy="loco", quant=QuantConfig(bits=8, mode="block"),
               hierarchical=True,
               stage2=SyncConfig(strategy="naive4",
                                 quant=QuantConfig(bits=4, mode="block"))),
    SyncConfig(strategy="naive4", quant=QuantConfig(bits=8, mode="tensor"),
               hierarchical=True),
    SyncConfig(strategy="onebit", hierarchical=True),
], ids=lambda c: f"{c.strategy}-{c.quant.bits}-{c.quant.mode}")
def test_hier_stage_bytes_match_arrays(cfg):
    """telemetry.hier_stage_bytes byte-matches what hierarchical_sync puts
    on each network: stage 1 = the bucket codec's wire arrays (gather
    leaves received from the Dd pod members), stage 2 = the stage-2
    codec's arrays for the pod-mean segment — the caveat 'hierarchical is
    reported as the flat path' is gone."""
    from repro.core import codec as codec_lib
    from repro.telemetry import wire as W

    pods, dd = 2, 2
    n = pods * dd * 512
    codec = codec_lib.get_codec(cfg)
    g = jax.random.normal(jax.random.PRNGKey(0), (n,)) * 1e-3
    wire, _ = codec.encode(g, codec.init_state(n))
    s1 = 0
    for name, leaf in codec.wire_shapes(n).items():
        nbytes = wire[name].size * wire[name].dtype.itemsize
        s1 += nbytes * (dd if leaf.comm == "gather" else 1)
    cfg2 = cfg.stage2_sync()
    codec2 = codec_lib.get_codec(cfg2)
    n2 = n // dd
    wire2, _ = codec2.encode(g[:n2], codec2.init_state(n2))
    s2 = 0
    for name, leaf in codec2.wire_shapes(n2).items():
        nbytes = wire2[name].size * wire2[name].dtype.itemsize
        s2 += nbytes * (pods if leaf.comm == "gather" else 1)
    assert W.hier_stage_bytes(n, cfg, pods, dd) == (s1, s2)


def test_plan_report_ici_dcn_split():
    """plan_report splits every bucket into ICI/DCN: flat buckets by
    destination row, hierarchical buckets as stage-1 vs stage-2 wire; the
    totals stay consistent with the flat-path convention."""
    from repro.telemetry import wire as W

    qf = QuantConfig(bits=4, mode="block")
    hier = SyncConfig(strategy="loco", quant=qf, hierarchical=True)
    flat = SyncConfig(strategy="loco", quant=qf)
    pods, dd = 2, 2
    seg = pods * dd * 512
    pplan = BK.ParamPlan(
        group="g", name="p", tensor_class="body", chunklen=1024, layers=1,
        buckets=(BK.Bucket(0, 0, 512, seg, hier),
                 BK.Bucket(1, 512, 512, seg, flat)))
    rep = W.plan_report(BK.SyncPlan(params=(pplan,)), pods=pods)
    hb, fb = rep.buckets
    assert hb.hierarchical and not fb.hierarchical
    # flat bucket: ici + dcn == its total wire, split by row destination
    assert fb.ici + fb.dcn == fb.wire
    assert fb.dcn == fb.wire // 2  # 2 of 4 rows leave the pod
    # hier bucket: stage 1 is the full codec wire; stage 2 is 8-bit block
    # over seg/dd elements: payload + f32 scale per 256-block
    s1, s2 = W.hier_stage_bytes(seg, hier, pods, dd)
    assert (hb.ici, hb.dcn) == (s1, s2)
    n2 = seg // dd
    assert s2 == n2 + n2 // 256 * 4
    assert rep.ici_bytes == hb.ici + fb.ici
    assert rep.dcn_bytes == hb.dcn + fb.dcn
    assert rep.bf16_dcn_bytes == 2 * 2 * seg * (pods - 1) // pods
    assert 0 < rep.dcn_ratio_vs_bf16 < 1
    assert "DCN" in W.format_report(rep)
    # single-pod degenerate split: everything ICI
    rep1 = W.plan_report(BK.SyncPlan(params=(pplan,)), pods=1)
    assert rep1.dcn_bytes == 0 and rep1.ici_bytes == rep1.total_wire


# ---------------------------------------------------------------------------
# build-time validation + hijack closure caching (satellites)
# ---------------------------------------------------------------------------


def test_validate_rejects_bad_combos_at_build():
    """_validate_sync_configs fails loudly, with the bucket named, for
    combos that used to fail deep inside tracing (ef21) or silently fall
    back to the flat exchange (hierarchical on a 1-axis mesh)."""
    from repro.core.flatparam import MeshTopo
    from repro.launch.steps import RunConfig, _validate_sync_configs

    topo1 = MeshTopo(dp_axes=("data",), tp_axis="model", dp=2, tp=2)
    topo2 = MeshTopo(dp_axes=("pod", "data"), tp_axis="model", dp=4, tp=2,
                     pods=2)
    hier = SyncConfig(strategy="loco", hierarchical=True)

    with pytest.raises(ValueError, match="ef21"):
        _validate_sync_configs(RunConfig(sync=SyncConfig(strategy="ef21")),
                               None, topo1)
    with pytest.raises(ValueError, match=r"\(pod, data\) mesh"):
        _validate_sync_configs(RunConfig(sync=hier), None, topo1)
    # a 2-axis mesh with a size-1 pod axis is equally pointless: stage 2
    # would requantize for zero DCN saving
    topo_pod1 = MeshTopo(dp_axes=("pod", "data"), tp_axis="model", dp=4,
                         tp=2, pods=1)
    with pytest.raises(ValueError, match="1 pod"):
        _validate_sync_configs(RunConfig(sync=hier), None, topo_pod1)
    with pytest.raises(ValueError, match="no meaning for the fp"):
        _validate_sync_configs(
            RunConfig(sync=SyncConfig(strategy="fp", hierarchical=True)),
            None, topo2)
    with pytest.raises(ValueError, match="stateless"):
        _validate_sync_configs(
            RunConfig(sync=dataclasses.replace(
                hier, stage2=SyncConfig(strategy="onebit"))), None, topo2)
    sr2 = SyncConfig(strategy="naive4",
                     quant=QuantConfig(bits=8, mode="block",
                                       stochastic_rounding=True))
    with pytest.raises(ValueError, match="stage-2 stochastic_rounding"):
        _validate_sync_configs(
            RunConfig(sync=dataclasses.replace(hier, stage2=sr2)),
            None, topo2)
    nested = SyncConfig(strategy="naive4", hierarchical=True)
    with pytest.raises(ValueError, match="not itself be hierarchical"):
        _validate_sync_configs(
            RunConfig(sync=dataclasses.replace(hier, stage2=nested)),
            None, topo2)
    # supported combo passes
    _validate_sync_configs(RunConfig(sync=hier), None, topo2)
    # and per-bucket configs are checked with the bucket in view
    pplan = BK.ParamPlan(
        group="blocks", name="wq", tensor_class="body", chunklen=512,
        layers=1, buckets=(BK.Bucket(0, 0, 512, 1024, hier),))
    with pytest.raises(ValueError, match=r"blocks/wq\[0\]"):
        _validate_sync_configs(RunConfig(sync=hier),
                               BK.SyncPlan(params=(pplan,)), topo1)


def test_gather_fp_closure_cached(mesh22):
    """gather_fp builds its custom_vjp once per dp-axes tuple (satellite:
    it used to rebuild the closure on every call; retrace-count pinned via
    the lru_cache miss counter across two separate traces)."""
    from repro.core import hijack

    hijack._make_gather_fp.cache_clear()
    n = 2 * 512
    x = jax.random.normal(jax.random.PRNGKey(2), (n,))

    def step(w, xx):
        def loss(w):
            # two call sites in one trace + a second trace below: still
            # one closure build
            a = gather_fp(w, ("data",)).astype(jnp.float32)
            b = gather_fp(w, ("data",)).astype(jnp.float32)
            return jnp.sum((a + b) * xx)
        return jax.grad(loss)(w)

    for seed in (0, 1):
        fn = jax.jit(jax.shard_map(
            step, mesh=mesh22, in_specs=(P("data"), P(None)),
            out_specs=P("data"), check_vma=False))
        fn(jnp.zeros((n,), jnp.bfloat16), x * (seed + 1))
    info = hijack._make_gather_fp.cache_info()
    assert info.misses == 1, info
    assert info.hits >= 3, info
    assert (hijack._make_gather_fp(("data",))
            is hijack._make_gather_fp(("data",)))


def test_hierarchical_with_kernels_matches_oracle(mesh_pod):
    """`use_kernels` dispatches the stage-1/stage-2 codecs through the
    registered Pallas fast paths inside the two-stage exchange; interpret
    mode must reproduce the jnp oracle bit-for-bit (same contract as the
    flat path, tests/test_codec.py)."""
    qf = QuantConfig(mode="block")
    base = SyncConfig(strategy="loco", quant=qf, hierarchical=True)
    kern = dataclasses.replace(base, use_kernels=True)
    N, n = 4, 4 * 512
    g = jax.random.normal(jax.random.PRNGKey(17), (N, n)) * 1e-3
    st = jnp.stack([init_state(base, n) for _ in range(N)])
    g_ref, st_ref = _dist_sync_once(mesh_pod, ("pod", "data"), base, g, st)
    g_k, st_k = _dist_sync_once(mesh_pod, ("pod", "data"), kern, g, st)
    np.testing.assert_array_equal(np.asarray(g_ref), np.asarray(g_k))
    np.testing.assert_array_equal(
        np.asarray(st_ref.astype(jnp.float32)),
        np.asarray(st_k.astype(jnp.float32)))


# ---------------------------------------------------------------------------
# ragged topk wire + cadence-aware scheduling (ISSUE 8)
# ---------------------------------------------------------------------------


def _dist_sync_step(mesh, dp_axes, cfg, g_nodes, state_nodes, step):
    """Like _dist_sync_once but threading the traced step scalar (the
    cadence gate's input)."""
    def body(g, st, s):
        g_shard, new_st = dist_sync(g.reshape(-1), st.reshape(-1), cfg,
                                    dp_axes, step=s)
        return all_gather_flat(g_shard, dp_axes), new_st[None]

    spec = P(dp_axes if len(dp_axes) > 1 else dp_axes[0])
    fn = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(spec, spec, P()),
        out_specs=(P(None), spec), check_vma=False))
    return fn(g_nodes, state_nodes, step)


def test_topk_full_capacity_matches_dense_bf16(mesh22):
    """topk at 100% capacity degenerates to the dense bf16 wire: every
    entry crosses as a (u16, bf16) pair, so the decoded mean equals the
    mean of the bf16-rounded compensated gradients bit-for-bit (the
    acceptance property of the ragged capacity form)."""
    cfg = SyncConfig(strategy="topk", topk_frac=1.0)
    N, n = 2, 2 * 512
    g = jax.random.normal(jax.random.PRNGKey(23), (N, n)) * 1e-3
    st = jnp.stack([init_state(cfg, n) for _ in range(N)])
    ghat, _ = _dist_sync_once(mesh22, ("data",), cfg, g, st)
    want = jnp.mean(g.astype(jnp.bfloat16).astype(jnp.float32), axis=0)
    np.testing.assert_array_equal(np.asarray(ghat), np.asarray(want))


def test_cadence_every1_transparent(mesh22):
    """The cadence gate at every=1 is bit-transparent: threading the step
    produces the same shards AND states as the legacy step-less path over
    two state-evolving rounds (so per-step callers may always pass it)."""
    cfg = SyncConfig(strategy="loco", quant=QuantConfig(mode="block"))
    N, n = 2, 2 * 512
    g = jax.random.normal(jax.random.PRNGKey(29), (N, n)) * 1e-3
    st_a = jnp.stack([init_state(cfg, n) for _ in range(N)])
    st_b = st_a
    for s in range(2):
        ga, st_a = _dist_sync_step(mesh22, ("data",), cfg, g * (s + 1),
                                   st_a, jnp.int32(s))
        gb, st_b = _dist_sync_once(mesh22, ("data",), cfg, g * (s + 1), st_b)
        np.testing.assert_array_equal(np.asarray(ga), np.asarray(gb))
        np.testing.assert_array_equal(
            np.asarray(st_a.astype(jnp.float32)),
            np.asarray(st_b.astype(jnp.float32)))


def test_cadence_every2_accumulates(mesh22):
    """every=2 semantics (DESIGN.md §16): the off-cadence step returns a
    zero shard and folds its gradient into the compensation-error state
    (the state IS the accumulator); the on-cadence step then equals the
    ungated sync fed the carried accumulator, bit for bit."""
    from repro.core import codec as codec_lib

    cfg = SyncConfig(strategy="loco", quant=QuantConfig(mode="block"),
                     every=2)
    N, n = 2, 2 * 512
    key = jax.random.PRNGKey(31)
    g0 = jax.random.normal(key, (N, n)) * 1e-3
    g1 = jax.random.normal(jax.random.fold_in(key, 1), (N, n)) * 1e-3
    st0 = jnp.stack([init_state(cfg, n) for _ in range(N)])

    sh0, st_acc = _dist_sync_step(mesh22, ("data",), cfg, g0, st0,
                                  jnp.int32(0))
    assert not np.any(np.asarray(sh0))
    codec = codec_lib.get_codec(cfg)
    acc_host = np.asarray(st_acc.astype(jnp.float32))
    for i in range(N):
        want = codec.state_encode(g0[i] + codec.state_decode(st0[i]))
        np.testing.assert_array_equal(
            acc_host[i], np.asarray(want.astype(jnp.float32)))

    sh1, st1 = _dist_sync_step(mesh22, ("data",), cfg, g1, st_acc,
                               jnp.int32(1))
    ref, st_ref = _dist_sync_once(mesh22, ("data",), cfg, g1, st_acc)
    np.testing.assert_array_equal(np.asarray(sh1), np.asarray(ref))
    np.testing.assert_array_equal(
        np.asarray(st1.astype(jnp.float32)),
        np.asarray(st_ref.astype(jnp.float32)))


def test_cadence_single_trace_across_period(mesh22):
    """The step is a traced scalar: one compiled function covers the whole
    cadence period (no retrace across steps 0..3 — the acceptance pin),
    with zero shards off-cadence and the flush firing on step every-1."""
    cfg = SyncConfig(strategy="loco", quant=QuantConfig(mode="block"),
                     every=4)
    N, n = 2, 2 * 512
    traces = []

    def body(g, st, s):
        traces.append(1)
        sh, ns = dist_sync(g.reshape(-1), st.reshape(-1), cfg, ("data",),
                           step=s)
        return all_gather_flat(sh, ("data",)), ns[None]

    fn = jax.jit(jax.shard_map(
        body, mesh=mesh22, in_specs=(P("data"), P("data"), P()),
        out_specs=(P(None), P("data")), check_vma=False))
    g = jax.random.normal(jax.random.PRNGKey(37), (N, n)) * 1e-3
    # place st as the step returns it, so every call sees one abstract value
    st = jax.device_put(jnp.stack([init_state(cfg, n) for _ in range(N)]),
                        NamedSharding(mesh22, P("data")))
    outs = []
    for s in range(4):
        full, st = fn(g, st, jnp.int32(s))
        outs.append(np.asarray(full))
    assert len(traces) == 1, len(traces)
    for s in range(3):
        assert not np.any(outs[s]), s
    assert np.any(outs[3])
    # the flush releases the whole period's accumulated gradient: roughly
    # 4x the per-step mean (f8 accumulator + 4-bit wire are lossy, so only
    # the magnitude is pinned, not the bits)
    want = np.asarray(jnp.mean(g, axis=0)) * 4
    err = np.abs(outs[3] - want).max()
    assert err < 0.25 * np.abs(want).max(), err


def test_tier_cadence_own_slice_bypass(mesh_pod):
    """Outer-tier cadence (tier.every=2): the off-cadence step skips the
    cross-pod exchange and each rank keeps its OWN pod's stage-1 mean (the
    DiLoCo-style local approximation, bit-exact vs the per-pod flat
    simulation); the on-cadence step equals the ungated hierarchical
    result bit for bit."""
    base = SyncConfig(strategy="loco", quant=QuantConfig(mode="block"),
                      hierarchical=True)
    gated = dataclasses.replace(
        base, tiers=(dataclasses.replace(sync_schedule(base)[0], every=2),))
    N, n = 4, 4 * 512
    g = jax.random.normal(jax.random.PRNGKey(41), (N, n)) * 1e-3
    st = jnp.stack([init_state(base, n) for _ in range(N)])

    # step 1 hits the cadence (1 % 2 == 1): normal two-stage result
    g_on, st_on = _dist_sync_step(mesh_pod, ("pod", "data"), gated, g, st,
                                  jnp.int32(1))
    g_ref, st_ref = _dist_sync_once(mesh_pod, ("pod", "data"), base, g, st)
    np.testing.assert_array_equal(np.asarray(g_on), np.asarray(g_ref))
    np.testing.assert_array_equal(
        np.asarray(st_on.astype(jnp.float32)),
        np.asarray(st_ref.astype(jnp.float32)))

    # step 0 is off-cadence: rank r = (p, d) keeps pod p's stage-1 mean of
    # chunk r — per pod, exactly the 2-node flat simulation's shard
    g_off, _ = _dist_sync_step(mesh_pod, ("pod", "data"), gated, g, st,
                               jnp.int32(0))
    flat = dataclasses.replace(base, hierarchical=False, tiers=None)
    want = np.empty((n,), np.float32)
    for p in range(2):
        rows = g[2 * p:2 * p + 2]
        ghat_pod, _ = sim_sync(rows, sim_init(flat, 2, n), jnp.int32(1), flat)
        # pod p's ranks own flat chunks 2p and 2p+1
        sl = slice(p * (n // 2), (p + 1) * (n // 2))
        want[sl] = np.asarray(ghat_pod)[sl]
    np.testing.assert_array_equal(np.asarray(g_off), want)


def test_three_tier_wan_schedule_bitexact(mesh_wan):
    """A 3-tier schedule (ICI codec -> DCN naive8 -> WAN topk) over the
    (wan, pod, data) mesh: with identical gradients on every rank, all
    group means collapse to the shared row, so the exchanged result equals
    the chained single-node codec round trips — bit-exact, slice
    boundaries included (512-aligned chunks preserve quant-block and
    top-k block edges)."""
    from repro.core import codec as codec_lib

    qb = QuantConfig(bits=8, mode="block")
    pod_tier = SyncTier(SyncConfig(strategy="naive4", quant=qb), every=1)
    wan_tier = SyncTier(SyncConfig(strategy="topk", topk_frac=0.25), every=1)
    cfg = SyncConfig(strategy="loco", quant=qb, hierarchical=True,
                     tiers=(pod_tier, wan_tier))
    N, n = 8, 8 * 512
    row = jax.random.normal(jax.random.PRNGKey(43), (n,)) * 1e-3
    g = jnp.tile(row[None], (N, 1))
    st = jnp.stack([init_state(cfg, n) for _ in range(N)])
    ghat, _ = _dist_sync_once(mesh_wan, ("wan", "pod", "data"), cfg, g, st)

    def roundtrip(c, x):
        codec = codec_lib.get_codec(c)
        wire, _ = codec.encode(x, codec.init_state(x.shape[0]))
        return codec.decode_mean({k: v[None] for k, v in wire.items()})

    x = roundtrip(cfg, row)                      # stage 1 (ICI, loco8)
    x = roundtrip(pod_tier.sync, x)              # tier 1 (DCN, naive8)
    x = roundtrip(wan_tier.sync, x)              # tier 2 (WAN, topk)
    np.testing.assert_array_equal(np.asarray(ghat), np.asarray(x))


def test_validate_rejects_cadence_and_tier_combos():
    """Build-time rejection of the ISSUE-8 combos: cadence on a stateless
    codec, reset mid-period, N-tier schedules on too-flat meshes, tier
    cadence under the coalesced exchange, and cadence/ragged buckets on
    the pipelined overlap schedule — each naming the bucket/tier and the
    escape hatch."""
    from repro.core.flatparam import MeshTopo
    from repro.launch.steps import RunConfig, _validate_sync_configs

    topo2 = MeshTopo(dp_axes=("pod", "data"), tp_axis="model", dp=4, tp=2,
                     pods=2)
    with pytest.raises(ValueError, match="has no state"):
        _validate_sync_configs(
            RunConfig(sync=SyncConfig(strategy="naive4", every=2)),
            None, topo2)
    with pytest.raises(ValueError, match="multiple of"):
        _validate_sync_configs(
            RunConfig(sync=SyncConfig(strategy="loco", every=3,
                                      reset_every=512)),
            None, topo2)
    # a 2-tier (pod + wan) schedule needs 3 dp axes with real wan groups
    qb = QuantConfig(bits=8, mode="block")
    wan = SyncConfig(
        strategy="loco", quant=qb, hierarchical=True,
        tiers=(SyncTier(SyncConfig(strategy="naive4", quant=qb), every=1),
               SyncTier(SyncConfig(strategy="topk"), every=16)))
    with pytest.raises(ValueError, match=r"--wans >= 2"):
        _validate_sync_configs(RunConfig(sync=wan), None, topo2)

    def plan_of(cfgs, D=4):
        buckets, off = [], 0
        for i, s in enumerate(cfgs):
            buckets.append(BK.Bucket(index=i, offset=off, chunk_elems=512,
                                     seg_elems=D * 512, sync=s))
            off += 512
        pp = BK.ParamPlan(group="blocks", name="wq", tensor_class="body",
                          chunklen=off, layers=1, buckets=tuple(buckets))
        return BK.SyncPlan(params=(pp,))

    # tier cadence rides only the monolithic exchange
    hier_cad = dataclasses.replace(
        SyncConfig(strategy="loco", quant=qb, hierarchical=True),
        tiers=(SyncTier(SyncConfig(strategy="naive4", quant=qb), every=4),))
    with pytest.raises(ValueError, match=r"--no-coalesce"):
        _validate_sync_configs(RunConfig(sync=hier_cad),
                               plan_of((hier_cad,)), topo2)
    _validate_sync_configs(RunConfig(sync=hier_cad, coalesce=False),
                           plan_of((hier_cad,)), topo2)
    # tier-0 cadence / ragged topk cannot gate the pipelined overlap
    # schedule's stage pieces (a piece cannot gate the whole accumulator)
    loco = SyncConfig(strategy="loco", quant=qb)
    cad = dataclasses.replace(loco, every=2)
    with pytest.raises(ValueError, match=r"--no-overlap"):
        _validate_sync_configs(
            RunConfig(sync=loco),
            plan_of((cad, SyncConfig(strategy="naive4",
                                     quant=QuantConfig(bits=8,
                                                       mode="tensor")),
                     SyncConfig(strategy="fp"))), topo2)
    topk = SyncConfig(strategy="topk")
    with pytest.raises(ValueError, match=r"--no-overlap"):
        _validate_sync_configs(
            RunConfig(sync=loco),
            plan_of((topk, SyncConfig(strategy="naive4",
                                      quant=QuantConfig(bits=8,
                                                        mode="tensor")),
                     SyncConfig(strategy="fp"))), topo2)
    # the escape hatch passes
    _validate_sync_configs(RunConfig(sync=loco, overlap=False),
                           plan_of((cad, loco)), topo2)
