"""The token traffic: non-iid per-agent streams, made on the device.

A copy of the Dirichlet generator of ``src/repro/data/federated_lm.py``
(kept here so that a change to the program cannot change the traffic):
each agent draws tokens from its own unigram distribution, a Dirichlet(α)
split of the vocabulary (small α: strongly non-iid agents), with a bigram
kick that raises the successor of the previous token, so sequences carry
learnable structure.

The benchmark draws a pool of ``pool_rounds`` rounds of ``H`` steps once,
in set-up, in one jitted call, and the window cycles through the pool.
Every row of the pool differs; speed does not depend on token values in
the dense models measured here.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

__all__ = ["agent_logits", "token_pool", "round_batches"]

SHIFT_STRENGTH = 1.0


def agent_logits(key: jax.Array, vocab: int, n_agents: int,
                 alpha: float) -> jax.Array:
    """(n_agents, vocab) unigram logits of a Dirichlet(α) vocabulary split."""
    probs = jax.random.dirichlet(key, jnp.full((vocab,), alpha),
                                 shape=(n_agents,))
    return jnp.log(probs + 1e-9)


def _sample_rows(keys: jax.Array, logits: jax.Array, seq_len: int):
    """(R, seq_len) tokens; row r follows ``logits[r]`` with the bigram
    kick."""
    vocab = logits.shape[-1]
    k0 = jax.vmap(lambda k: jax.random.split(k)[0])(keys)
    kseq = jax.vmap(lambda k: jax.random.split(k)[1])(keys)
    first = jax.vmap(jax.random.categorical)(k0, logits)          # (R,)
    step_keys = jax.vmap(lambda k: jax.random.split(k, seq_len - 1))(kseq)

    def step(tok, ks):
        kick = jax.nn.one_hot((tok + 1) % vocab, vocab)
        nxt = jax.vmap(jax.random.categorical)(
            ks, logits + 4.0 * SHIFT_STRENGTH * kick)
        return nxt, nxt

    _, rest = jax.lax.scan(step, first, step_keys.swapaxes(0, 1))
    return jnp.concatenate([first[None], rest], axis=0).T.astype(jnp.int32)


@partial(jax.jit, static_argnames=("vocab", "n_agents", "batch", "seq_len",
                                   "h", "rounds", "alpha"))
def token_pool(key: jax.Array, *, vocab: int, n_agents: int, batch: int,
               seq_len: int, h: int, rounds: int, alpha: float) -> jax.Array:
    """(rounds, h, n_agents, batch, seq_len) int32 tokens."""
    k_dist, k_rows = jax.random.split(key)
    logits = agent_logits(k_dist, vocab, n_agents, alpha)
    shape = (rounds, h, n_agents, batch)
    agent = jnp.broadcast_to(jnp.arange(n_agents)[None, None, :, None],
                             shape).reshape(-1)
    keys = jax.random.split(k_rows, agent.shape[0])
    rows = _sample_rows(keys, logits[agent], seq_len)
    return rows.reshape(shape + (seq_len,))


def round_batches(pool: jax.Array) -> list[dict]:
    """The pool as one executor batch per round — {'tokens', 'positions'},
    each (h, n_agents, batch, seq_len), as ``launch/train.py`` feeds a
    fused round."""
    seq_len = pool.shape[-1]
    positions = jnp.broadcast_to(jnp.arange(seq_len, dtype=jnp.int32),
                                 pool.shape[1:])
    return [{"tokens": pool[r], "positions": positions}
            for r in range(pool.shape[0])]
