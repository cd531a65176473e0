"""The one traffic generator: reads a mix's parameters, draws from the seed.

Training mixes give rows of an order-1 Markov chain over the vocabulary
(each token has ``branch`` successors with Dirichlet(0.5) weights), made on
the device. Every seed gets the same batch shapes; the seed draws the
chain and the token ids.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchlib.weights import seed_key


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _markov(key, rows, n, vocab, branch):
    k_succ, k_prob, k_first, k_u = jax.random.split(key, 4)
    succ = jax.random.randint(k_succ, (vocab, branch), 0, vocab, jnp.int32)
    probs = jax.random.dirichlet(k_prob, jnp.full((branch,), 0.5),
                                 (vocab,))
    cum = jnp.cumsum(probs, -1)
    first = jax.random.randint(k_first, (rows,), 0, vocab, jnp.int32)
    u = jax.random.uniform(k_u, (n, rows))

    def step(cur, u_t):
        choice = jnp.minimum((u_t[:, None] > cum[cur]).sum(-1), branch - 1)
        nxt = succ[cur, choice]
        return nxt, nxt

    _, rest = jax.lax.scan(step, first, u)
    return jnp.concatenate([first[None], rest], 0).T        # (rows, n + 1)


def markov_batches(seed: int, count: int, batch: int, n: int, vocab: int,
                   branch: int = 8):
    """``count`` batches of ``batch`` rows x ``n`` tokens, all rows
    different, as ``{"tokens", "labels"}`` dicts on the device."""
    rows = _markov(jax.random.fold_in(seed_key(seed), 2), count * batch, n,
                   vocab, branch)
    rows = rows.reshape(count, batch, n + 1)
    return [{"tokens": rows[i, :, :-1], "labels": rows[i, :, 1:]}
            for i in range(count)]

