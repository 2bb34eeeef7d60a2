"""End-to-end training-behavior tests (the paper's core quality claims,
scaled down): LoCo trains as well as fp; naive 4-bit is worse; checkpoints
resume bit-exactly; kernels path == jnp path."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import checkpoint as CKPT
from repro.configs.base import ShapeConfig, get_arch, reduced
from repro.core.loco import SyncConfig
from repro.core.quantizer import QuantConfig
from repro.data.synthetic import DataConfig, make_batch_fn
from repro.launch.steps import RunConfig, make_init, make_train_step

CFG = reduced(get_arch("llama2-400m"))
SHAPE = ShapeConfig("tiny", seq_len=32, global_batch=8, kind="train")


def _train(mesh, sync: SyncConfig, steps=12, seed=0):
    run = RunConfig(sync=sync, optimizer="adam", microbatch=2,
                    total_steps=steps, warmup_steps=2, lr=2e-3)
    init_fn, _ = make_init(CFG, run, mesh)
    chunks, states, opt = init_fn(jax.random.PRNGKey(seed))
    bundle = make_train_step(CFG, run, mesh, SHAPE)
    bf = make_batch_fn(DataConfig(vocab=CFG.vocab, seq_len=SHAPE.seq_len,
                                  global_batch=SHAPE.global_batch, seed=seed))
    losses = []
    for i in range(steps):
        chunks, states, opt, m = bundle.fn(chunks, states, opt, jnp.int32(i),
                                           bf(jnp.int32(i)))
        losses.append(float(m["loss"]))
    return np.array(losses), (chunks, states, opt)


def test_loss_decreases(mesh22):
    losses, _ = _train(mesh22, SyncConfig(strategy="fp"))
    assert losses[-1] < losses[0] - 0.3, losses


def test_loco_matches_fp_quality(mesh22):
    """Paper Tables 3/5 claim at micro scale: LoCo's loss trajectory tracks
    full-precision closely; naive 4-bit with a bad fixed scale does not."""
    l_fp, _ = _train(mesh22, SyncConfig(strategy="fp"))
    l_loco, _ = _train(mesh22, SyncConfig(
        strategy="loco", quant=QuantConfig(mode="block")))
    gap_loco = abs(l_loco[-1] - l_fp[-1])
    assert gap_loco < 0.15, (l_fp[-1], l_loco[-1])

    l_naive, _ = _train(mesh22, SyncConfig(
        strategy="naive4", quant=QuantConfig(mode="fixed", scale=2.0**9)))
    gap_naive = abs(l_naive[-1] - l_fp[-1])
    assert gap_naive > 2 * gap_loco, (l_fp[-1], l_loco[-1], l_naive[-1])


def test_kernel_path_matches_jnp_path(mesh22):
    base = SyncConfig(strategy="loco", quant=QuantConfig(mode="block"))
    l_jnp, _ = _train(mesh22, base, steps=6)
    l_k, _ = _train(mesh22, dataclasses.replace(base, use_kernels=True), steps=6)
    np.testing.assert_allclose(l_jnp, l_k, atol=5e-3)


def test_checkpoint_resume_bit_exact(mesh22, tmp_path):
    sync = SyncConfig(strategy="loco", quant=QuantConfig(mode="block"))
    run = RunConfig(sync=sync, optimizer="adam", microbatch=2,
                    total_steps=10, warmup_steps=1, lr=1e-3)
    init_fn, _ = make_init(CFG, run, mesh22)
    chunks, states, opt = init_fn(jax.random.PRNGKey(0))
    bundle = make_train_step(CFG, run, mesh22, SHAPE)
    bf = make_batch_fn(DataConfig(vocab=CFG.vocab, seq_len=SHAPE.seq_len,
                                  global_batch=SHAPE.global_batch))
    for i in range(3):
        chunks, states, opt, _ = bundle.fn(chunks, states, opt, jnp.int32(i),
                                           bf(jnp.int32(i)))
    CKPT.save(str(tmp_path), 3, {"chunks": chunks, "states": states, "opt": opt})
    # continue two more steps
    c1, s1, o1 = chunks, states, opt
    for i in range(3, 5):
        c1, s1, o1, m1 = bundle.fn(c1, s1, o1, jnp.int32(i), bf(jnp.int32(i)))
    # restore and replay
    st = CKPT.restore(str(tmp_path), 3, {"chunks": chunks, "states": states, "opt": opt})
    c2, s2, o2 = st["chunks"], st["states"], st["opt"]
    for i in range(3, 5):
        c2, s2, o2, m2 = bundle.fn(c2, s2, o2, jnp.int32(i), bf(jnp.int32(i)))
    assert float(m1["loss"]) == float(m2["loss"])
    for a, b in zip(jax.tree.leaves(c1), jax.tree.leaves(c2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_multipod_mesh_trains(mesh_pod):
    """The ('pod','data') joint dp group trains and syncs correctly."""
    losses, _ = _train(mesh_pod, SyncConfig(strategy="loco",
                                            quant=QuantConfig(mode="block")), steps=6)
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_location(monkeypatch, tmp_path, from_env):
    """The persistent compile cache stays where JAX_COMPILATION_CACHE_DIR
    puts it (nothing is set), and otherwise goes to ``.jax_cache/`` at the
    checkout root -- a fixed path, so a later run finds it again."""
    from pathlib import Path

    from repro.launch import train as T

    before = jax.config.jax_compilation_cache_dir
    keyed = jax.config.jax_compilation_cache_include_metadata_in_key
    canon = jax.config.jax_hlo_source_file_canonicalization_regex
    try:
        if from_env:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
            assert T.use_compile_cache() == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == before
        else:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            want = str(Path(__file__).resolve().parents[1] / ".jax_cache")
            assert T.use_compile_cache() == want
            assert jax.config.jax_compilation_cache_dir == want
            assert T.use_compile_cache() == want  # idempotent
        # the key holds the HLO metadata, with source files named from the
        # checkout root: the same code elsewhere finds the same entries
        assert jax.config.jax_compilation_cache_include_metadata_in_key
        root = str(Path(__file__).resolve().parents[1])
        text = jax.jit(lambda x: x + 1).lower(1.0).as_text(debug_info=True)
        assert "tests/test_train_integration.py" in text
        assert root not in text
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          keyed)
        jax.config.update("jax_hlo_source_file_canonicalization_regex",
                          canon)
