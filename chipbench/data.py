"""Seeded inputs: the PRNG key of a seed, and the ring of token batches.

The batch generator is a copy of ``repro.data.synthetic.make_batch_fn``
(a cluster id walks a deterministic cycle; tokens are drawn from a
cluster-conditional zipf-ish distribution, so a language model has
learnable structure).  Two departures, neither of which changes the
distribution: the draws run one row at a time (``lax.map``), so the Gumbel
noise of a 122,753-token vocabulary never needs ``B x S x V`` floats at
once, and the whole ring is made by one jitted call in set-up.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

N_CLUSTERS = 32


def seed_key(seed: int) -> jax.Array:
    """A PRNG key that keeps every bit of a seed of up to 64 bits.

    ``jax.random.PRNGKey`` keeps only the low 32 bits of a large seed, so
    two seeds that differ above bit 31 would share their inputs.
    """
    if not 0 <= seed < 2**64:
        raise ValueError(f"seed {seed} is not a whole number of 64 bits")
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), seed >> 32)


def _zipf_logits(vocab: int, key) -> jax.Array:
    ranks = jnp.arange(1, vocab + 1, dtype=jnp.float32)
    return -1.1 * jnp.log(ranks) + 0.3 * jax.random.normal(key, (vocab,))


def make_ring(key: jax.Array, vocab: int, seq_len: int, batch: int,
              ring: int) -> jax.Array:
    """``(ring, batch, seq_len + 1)`` int32 tokens; every row differs."""
    table_key, stream_key = jax.random.split(key)
    tables = jax.vmap(lambda k: _zipf_logits(vocab, k))(
        jax.random.split(table_key, N_CLUSTERS))                  # (C, V)
    S = seq_len + 1

    def row(k):
        kc, kt = jax.random.split(k)
        start = jax.random.randint(kc, (), 0, N_CLUSTERS)
        clusters = (start + jnp.arange(S) // 8) % N_CLUSTERS
        keys = jax.random.split(kt, S)
        return jax.vmap(lambda kk, c: jax.random.categorical(kk, tables[c]))(
            keys, clusters).astype(jnp.int32)

    keys = jax.random.split(stream_key, ring * batch)
    return jax.lax.map(row, keys).reshape(ring, batch, S)
