"""Synthetic heterogeneous federated LM data pipeline.

FedDec's setting needs *per-agent, non-iid* data streams.  For language-model
experiments we synthesise them the standard FL-benchmark way: each agent i
draws tokens from its own unigram-mixture distribution built from a Dirichlet
split of the vocabulary (small Dirichlet α ⇒ strongly non-iid, mirroring the
paper's c_i = 2^i heterogeneity), with a Markov bigram kick so sequences have
learnable structure.

The pipeline is an infinite, deterministic, jax-PRNG-driven stream — every
batch is reproducible from (seed, step) with no host state, so the training
loop stays pure.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

__all__ = ["FederatedLMData", "make_federated_lm"]


@dataclasses.dataclass(frozen=True)
class FederatedLMData:
    """Per-agent token-stream sampler."""

    vocab_size: int
    n_agents: int
    seq_len: int
    agent_logits: jax.Array    # (n_agents, vocab) unigram logits
    shift_strength: float      # bigram kick: P(t+1 | t) ∝ exp(logits + s·roll)

    def sample_agent(self, key: jax.Array, agent: jax.Array,
                     batch: int) -> jax.Array:
        """(batch, seq_len) tokens for one agent."""
        logits = self.agent_logits[agent]

        def step(tok, k):
            # bigram kick: successor token gets a logit boost ⇒ sequences
            # carry learnable next-token structure beyond the unigram mix
            kick = jax.nn.one_hot((tok + 1) % self.vocab_size,
                                  self.vocab_size)
            nxt = jax.random.categorical(
                k, logits + 4.0 * self.shift_strength * kick, axis=-1)
            return nxt, nxt

        k0, kseq = jax.random.split(key)
        first = jax.random.categorical(k0, jnp.broadcast_to(
            logits, (batch, self.vocab_size)), axis=-1)
        ks = jax.random.split(kseq, self.seq_len - 1)
        _, rest = jax.lax.scan(step, first, ks)
        return jnp.concatenate([first[None], rest], axis=0).T  # (B, S)

    def sample(self, key: jax.Array, per_agent_batch: int) -> jax.Array:
        """(n_agents, per_agent_batch, seq_len) — one federated batch."""
        keys = jax.random.split(key, self.n_agents)
        agents = jnp.arange(self.n_agents)
        return jax.vmap(self.sample_agent, in_axes=(0, 0, None))(
            keys, agents, per_agent_batch)


def make_federated_lm(vocab_size: int, n_agents: int, seq_len: int,
                      alpha: float = 0.3, shift_strength: float = 1.0,
                      seed: int = 0) -> FederatedLMData:
    """Build the per-agent distributions.

    Args:
      alpha: Dirichlet concentration; smaller ⇒ more heterogeneous agents
        (α→∞ recovers iid).
    """
    key = jax.random.key(seed)
    probs = jax.random.dirichlet(
        key, jnp.full((vocab_size,), alpha), shape=(n_agents,))
    logits = jnp.log(probs + 1e-9)
    return FederatedLMData(vocab_size=vocab_size, n_agents=n_agents,
                           seq_len=seq_len, agent_logits=logits,
                           shift_strength=shift_strength)
