"""Token batches made from the seed.

``token_batch`` is a copy of ``repro.data.synthetic.token_batch``, kept here
so that a change to the program's data code cannot move the traffic."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def token_batch(key: jax.Array, vocab: int, batch: int, seq: int,
                structure: int = 97) -> dict:
    """One batch of pseudo-text: Markov-ish tokens so the loss is learnable
    (next token correlates with current), not pure noise."""
    k1, k2 = jax.random.split(key)
    base = jax.random.randint(k1, (batch, seq), 0, vocab)
    shifted = (base * 31 + structure) % vocab
    noise = jax.random.bernoulli(k2, 0.25, (batch, seq))
    tokens = jnp.where(noise, base, jnp.roll(shifted, 1, axis=1))
    return {"tokens": tokens}


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole seed up to 2**63: ``jax.random.key`` keeps
    only the low 32 bits, so the high bits are folded in."""
    if not 0 <= seed < 2**63:
        raise SystemExit(f"--seed {seed} is outside [0, 2**63)")
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def streams(seed: int) -> dict:
    """The run's independent key streams: weights, data rows, the program's
    step keys and the reference's own draws."""
    names = ("weights", "data", "step", "reference")
    keys = jax.random.split(seed_key(seed), len(names))
    return dict(zip(names, keys))


def step_feed(vocab: int, batch: int, seq: int):
    """``feed(keys, i) -> (batch, key)`` for step ``i`` of the run whose
    ``streams`` are ``keys``: every step gets rows of its own, so no two
    steps of a run see the same tokens."""
    def feed(keys, i):
        return (token_batch(jax.random.fold_in(keys["data"], i), vocab,
                            batch, seq),
                jax.random.fold_in(keys["step"], i))
    return feed
