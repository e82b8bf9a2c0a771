"""Training traffic: a ring of distinct token batches made on the device.

Tokens are drawn from Zipf unigrams, one seeded permutation of the
vocabulary per domain, and each row belongs to one domain: skewed token
frequencies press the router toward collapse (BIP has real work to do), and
rows of one batch differ in their statistics.

Mix parameters: `ring` batches of `batch` rows of `seq_len` + 1 tokens,
Zipf exponent `zipf_a`, `domains` vocabulary permutations.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def make(mix: dict, vocab_size: int, key) -> jnp.ndarray:
    """(ring, batch, seq_len + 1) int32 tokens; call under jax.jit.

    Row r of ring entry i is drawn from domain (i·batch + r) mod domains.
    Inverse-CDF sampling keeps the working set at the size of the output."""
    ring, b, s = mix["ring"], mix["batch"], mix["seq_len"] + 1
    n_dom = mix["domains"]
    ranks = jnp.arange(1, vocab_size + 1, dtype=jnp.float32)
    probs = ranks ** (-mix["zipf_a"])
    cdf = jnp.cumsum(probs / jnp.sum(probs))
    k_perm, k_draw = jax.random.split(key)
    perms = jnp.stack([jax.random.permutation(jax.random.fold_in(k_perm, i), vocab_size)
                       for i in range(n_dom)])  # (domains, vocab)
    u = jax.random.uniform(k_draw, (ring, b, s))
    rank = jnp.minimum(jnp.searchsorted(cdf, u), vocab_size - 1)
    dom = (jnp.arange(ring)[:, None] * b + jnp.arange(b)[None, :]) % n_dom  # (ring, b)
    return jnp.take_along_axis(perms[dom], rank, axis=-1).astype(jnp.int32)
