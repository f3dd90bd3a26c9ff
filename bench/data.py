"""Seeded synthetic language-model batches: the benchmark's traffic source.

A copy of the repository's ``SyntheticLM`` generator, kept with the
benchmark so that no change to the program can change what the benchmark
feeds it.  Tokens follow an order-2 Markov chain over ``n_states`` ids:
next = (5 * prev + 3 * prev2 + noise) mod n_states, noise in {0, 1, 2}.
A batch is a pure function of (seed, index), so every row of every
microbatch differs and a reference can regenerate any of them.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class SyntheticLM:
    vocab_size: int
    seq_len: int
    batch_size: int
    seed: int = 0
    n_states: int = 64

    def batch(self, index: int) -> dict:
        """``{"tokens", "labels"}``, each int32 ``[batch_size, seq_len]``."""
        key = jax.random.fold_in(jax.random.PRNGKey(self.seed), index)
        k1, k2 = jax.random.split(key)
        n = min(self.n_states, self.vocab_size)
        x0 = jax.random.randint(k1, (self.batch_size, 2), 0, n)
        noise = jax.random.randint(k2, (self.batch_size, self.seq_len + 1),
                                   0, 3)

        def step(carry, eps):
            p1, p2 = carry
            nxt = (5 * p1 + 3 * p2 + eps) % n
            return (nxt, p1), nxt

        _, toks = jax.lax.scan(step, (x0[:, 0], x0[:, 1]),
                               noise.swapaxes(0, 1))
        toks = toks.swapaxes(0, 1).astype(jnp.int32)
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
