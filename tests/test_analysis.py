"""HLO static analyzer: exact on loop-free modules, trip-aware on scans."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.hlo_stats import analyze, parse_computations
from repro.analysis.roofline import roofline_terms


def _cost_analysis(comp):
    ca = comp.cost_analysis()
    return ca[0] if isinstance(ca, (list, tuple)) else ca  # pre-0.5 JAX: list


def test_matches_cost_analysis_loop_free():
    def f(a, b):
        return (a @ b).sum()

    a = jax.ShapeDtypeStruct((256, 512), jnp.float32)
    b = jax.ShapeDtypeStruct((512, 128), jnp.float32)
    comp = jax.jit(f).lower(a, b).compile()
    st = analyze(comp.as_text())
    assert st.flops == 2 * 256 * 512 * 128
    ca = _cost_analysis(comp)
    # bytes definition matches XLA's on unfused modules
    # ours is an estimate (elementwise ops count result-only); allow 25%
    np.testing.assert_allclose(st.bytes, ca["bytes accessed"], rtol=0.25)


def test_scan_trip_count_multiplies():
    def g(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, None, length=10)
        return y

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    w = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    comp = jax.jit(g).lower(x, w).compile()
    st = analyze(comp.as_text())
    assert st.flops == 10 * 2 * 64**3
    ca = _cost_analysis(comp)
    assert ca["flops"] < st.flops / 5  # the undercount this module fixes


def test_nested_scan():
    def h(x, w):
        def outer(c, _):
            def inner(ci, _):
                return ci @ w, None
            ci, _ = jax.lax.scan(inner, c, None, length=3)
            return ci, None
        y, _ = jax.lax.scan(outer, x, None, length=4)
        return y

    x = jax.ShapeDtypeStruct((32, 32), jnp.float32)
    w = jax.ShapeDtypeStruct((32, 32), jnp.float32)
    comp = jax.jit(h).lower(x, w).compile()
    st = analyze(comp.as_text())
    assert st.flops == 12 * 2 * 32**3


def test_parse_computations_finds_entry():
    def f(a):
        return a * 2

    comp = jax.jit(f).lower(jax.ShapeDtypeStruct((8,), jnp.float32)).compile()
    comps, entry = parse_computations(comp.as_text())
    assert entry in comps


def test_roofline_terms_dominance():
    t = roofline_terms(flops=197e12, hbm_bytes=0.0, wire_bytes=0.0)
    assert t["dominant"] == "compute_s" and abs(t["compute_s"] - 1.0) < 1e-9
    t = roofline_terms(flops=0.0, hbm_bytes=819e9, wire_bytes=25e9)
    assert t["dominant"] == "memory_s"


def test_collective_launch_counts_loop_free(mesh22):
    """collective_launches counts LAUNCHES per kind exactly on a
    hand-countable loop-free module (satellite: launch counts, not just
    bytes, are the number the wire coalescer drives down)."""
    from jax.sharding import PartitionSpec as P

    from repro.analysis.hlo_stats import collective_launches

    def body(x):
        a = jax.lax.all_gather(x, "data", tiled=True)
        b = jax.lax.psum_scatter(a, "data", tiled=True)
        c = jax.lax.psum_scatter(b * 2.0, "data", tiled=True)
        return jax.lax.all_gather(c, "data", tiled=True)

    fn = jax.jit(jax.shard_map(body, mesh=mesh22, in_specs=P("data"),
                               out_specs=P(None), check_vma=False))
    txt = fn.lower(jnp.zeros((1024,), jnp.float32)).compile().as_text()
    counts = collective_launches(txt)
    assert counts.get("all-gather", 0) == 2, counts
    assert counts.get("reduce-scatter", 0) == 2, counts
    assert counts.get("all-to-all", 0) == 0, counts


def test_collective_launch_counts_trip_weighted(mesh22):
    """Launch counts inside a scan body multiply by the trip count, same
    as the byte accounting."""
    from jax.sharding import PartitionSpec as P

    from repro.analysis.hlo_stats import collective_launches

    def body(x):
        def f(c, _):
            return jax.lax.psum(c, "data"), None
        y, _ = jax.lax.scan(f, x, None, length=5)
        return y

    fn = jax.jit(jax.shard_map(body, mesh=mesh22, in_specs=P("data"),
                               out_specs=P("data"), check_vma=False))
    txt = fn.lower(jnp.zeros((64,), jnp.float32)).compile().as_text()
    counts = collective_launches(txt)
    assert counts.get("all-reduce", 0) == 5, counts
