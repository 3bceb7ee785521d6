"""Compressed gossip: quantized / sparsified peer exchange with error feedback.

FedDec's gains grow with gossip frequency, but every inter-agent exchange
pays O(|E|·D) bytes — on the sharded engine that is the ppermute halo
traffic, the dominant collective cost at scale.  This module is the §Perf
iteration A2 subsystem: the gossip *payload* is compressed while the local
updates stay full precision, with a CHOCO-style **error-feedback residual**
so the quantization error is carried into the next exchange instead of being
lost (the standard fix that keeps compressed decentralized averaging
convergent — see the compressed-gossip survey in PAPERS.md).

Semantics (engine-independent, shared by the tree, flat and sharded paths):
with ``p_i`` the post-local-update iterate (Algorithm 1's x_i^{t+1/2}) and
``e_i`` the carried residual,

    u_i  = p_i + e_i                  # error-compensated payload
    s_i  = decode(encode(u_i))        # what the wire carries, dequantized
    e_i' = u_i − s_i                  # residual for the next step
    y_i  = Σ_j W_ij s_j + W_ii (p_i − s_i)
         = W_ii p_i + Σ_{j≠i} W_ij s_j

i.e. every agent mixes its neighbours' *compressed* values but keeps its own
iterate at full precision.  With the identity compressor s = u = p (residual
stays 0), the correction term is exactly 0 and ``y = W p`` — the uncompressed
trajectory.  ``gossip_compress='none'`` skips this machinery entirely (no
residual state, bit-identical code path).

Compressors (all per-row over the flat (n, D) layout — row i is agent i's
full parameter vector, so per-row statistics are per-agent statistics):

  * ``identity``  — s = u; exercises the EF plumbing, wire = D·b bytes/row;
  * ``bf16``      — round-to-nearest bf16 cast; 2·D bytes/row;
  * ``int8``      — stochastic-rounding int8 with one f32 scale per row
    (scale = max|u_row|/127; q = ⌊u/scale + noise⌋, noise ~ U[0,1)):
    unbiased (E[s] = u) with |s − u| ≤ scale, D + 4 bytes/row — a 4×
    payload cut;
  * ``topk:R``    — keep the ⌈R·D⌉ largest-magnitude entries per row
    (values + int32 indices): R·D·(b + 4) bytes/row.

On the sharded engine the halo exchange really moves the encoded payload
(int8 buffer + scales / top-k values + indices) through ``ppermute`` — the
collective bytes in the compiled HLO shrink accordingly; the flat and tree
engines apply encode→decode around their whole-buffer / leaf-wise mix (one
device: there is no wire, the compressed *semantics* are what is shared).
The int8 flat path fuses quantize→mix→dequantize into one Pallas streaming
kernel (kernels/compress_mix.py) when ``gossip_impl='pallas'``.

Cost model: :func:`repro.launch.analysis.compress_row_bytes` /
``compressed_halo_cost_model``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

__all__ = ["Compressor", "IdentityCompressor", "Bf16Compressor",
           "Int8Compressor", "TopKCompressor", "parse_compress",
           "COMPRESS_CHOICES", "init_residual", "init_residual_tree",
           "make_flat_ef_gossip", "make_tree_ef_gossip"]

# canonical spellings for CLI help; 'topk:R' takes any ratio 0 < R <= 1
COMPRESS_CHOICES = ("none", "identity", "bf16", "int8", "topk:R")


def _row_noise(keys: jax.Array, d: int) -> jax.Array:
    """(n, d) U[0,1) noise, one independent stream per agent row.

    Derived from per-agent keys (the same ``split(key_c, n_agents)`` array
    the engines row-slice), so the flat and sharded engines draw identical
    noise for agent i regardless of which shard owns the row.
    """
    return jax.vmap(lambda k: jax.random.uniform(k, (d,)))(keys)


@dataclasses.dataclass(frozen=True)
class Compressor:
    """Base: encode (n, d) → wire payload pytree; decode back to values.

    ``decode(encode(u))`` is the dequantized s the mix consumes; the wire
    moves the *encoded* payload (what the sharded halo actually ppermutes).
    ``needs_key`` marks stochastic codecs (int8 rounding noise).
    """

    name: str = "identity"
    needs_key: bool = False

    def encode(self, keys: jax.Array | None, u: jax.Array) -> Any:
        raise NotImplementedError

    def decode(self, payload: Any, dtype, d: int | None = None) -> jax.Array:
        """Payload → dequantized values.  ``d`` is the row width — payloads
        are pure array pytrees (they travel through ppermute), so codecs
        that drop columns (top-k) cannot infer it from the payload."""
        raise NotImplementedError

    def wire_bytes_per_row(self, d: int, param_bytes: int = 4) -> float:
        """Analytic payload bytes per agent row (the cost-model column)."""
        raise NotImplementedError


@dataclasses.dataclass(frozen=True)
class IdentityCompressor(Compressor):
    name: str = "identity"

    def encode(self, keys, u):
        return u

    def decode(self, payload, dtype, d=None):
        return payload.astype(dtype)

    def wire_bytes_per_row(self, d, param_bytes=4):
        return float(d * param_bytes)


@dataclasses.dataclass(frozen=True)
class Bf16Compressor(Compressor):
    name: str = "bf16"

    def encode(self, keys, u):
        return u.astype(jnp.bfloat16)

    def decode(self, payload, dtype, d=None):
        return payload.astype(dtype)

    def wire_bytes_per_row(self, d, param_bytes=4):
        return 2.0 * d


@dataclasses.dataclass(frozen=True)
class Int8Compressor(Compressor):
    """Stochastic-rounding int8 with one f32 scale per row.

    q = clip(⌊u/scale + noise⌋, −127, 127) with noise ~ U[0,1) is unbiased
    (E[⌊y + U⌋] = y for |y| ≤ 127) and |q·scale − u| ≤ scale elementwise —
    both property-tested in tests/test_compress.py.
    """

    name: str = "int8"
    needs_key: bool = True

    @staticmethod
    def row_scale(u: jax.Array) -> jax.Array:
        """(n,) per-row scale max|u_row|/127; 1 on all-zero rows (any
        positive value works — q is then exactly 0)."""
        s = jnp.max(jnp.abs(u.astype(jnp.float32)), axis=1) / 127.0
        return jnp.where(s > 0, s, 1.0)

    def encode(self, keys, u):
        uf = u.astype(jnp.float32)
        scale = self.row_scale(uf)
        noise = _row_noise(keys, u.shape[1])
        q = jnp.clip(jnp.floor(uf / scale[:, None] + noise), -127.0, 127.0)
        return {"q": q.astype(jnp.int8), "scale": scale}

    def decode(self, payload, dtype, d=None):
        s = payload["q"].astype(jnp.float32) * payload["scale"][:, None]
        return s.astype(dtype)

    def wire_bytes_per_row(self, d, param_bytes=4):
        return float(d) + 4.0  # int8 payload + one f32 scale


@dataclasses.dataclass(frozen=True)
class TopKCompressor(Compressor):
    """Magnitude top-k sparsification: keep ⌈R·d⌉ entries per row.

    Deterministic (ties broken by index, identically on every engine); the
    wire carries the kept values plus their int32 column indices.
    """

    name: str = "topk"
    ratio: float = 0.1

    def k_of(self, d: int) -> int:
        return max(1, min(d, int(round(self.ratio * d))))

    def encode(self, keys, u):
        k = self.k_of(u.shape[1])
        _, idx = jax.lax.top_k(jnp.abs(u.astype(jnp.float32)), k)
        vals = jnp.take_along_axis(u, idx, axis=1)
        return {"v": vals, "i": idx.astype(jnp.int32)}

    def decode(self, payload, dtype, d=None):
        assert d is not None, "top-k decode needs the row width d"
        vals, idx = payload["v"], payload["i"]
        n = vals.shape[0]
        rows = jnp.arange(n, dtype=jnp.int32)[:, None]
        out = jnp.zeros((n, d), dtype)
        return out.at[rows, idx].set(vals.astype(dtype))

    def wire_bytes_per_row(self, d, param_bytes=4):
        return float(self.k_of(d)) * (param_bytes + 4.0)


def parse_compress(spec: str) -> Compressor | None:
    """'none' | 'identity' | 'bf16' | 'int8' | 'topk:R' → Compressor.

    'none' returns None: the engines then take the uncompressed code path
    (no residual state, bit-identical to pre-compression trajectories).
    """
    if spec == "none":
        return None
    if spec == "identity":
        return IdentityCompressor()
    if spec == "bf16":
        return Bf16Compressor()
    if spec == "int8":
        return Int8Compressor()
    if spec.startswith("topk:"):
        try:
            ratio = float(spec[5:])
        except ValueError:
            ratio = -1.0
        if not 0.0 < ratio <= 1.0:
            raise ValueError(
                f"topk ratio must be in (0, 1]: {spec!r}")
        return TopKCompressor(ratio=ratio)
    raise ValueError(
        f"unknown gossip_compress {spec!r}; choose from "
        f"{'|'.join(COMPRESS_CHOICES)}")


def init_residual(compressor: Compressor | None, n_agents: int, d: int,
                  dtype) -> Any:
    """Zero EF residual buffer for the flat layout; () when uncompressed."""
    if compressor is None:
        return ()
    return jnp.zeros((n_agents, d), dtype)


def init_residual_tree(compressor: Compressor | None, stacked: Any) -> Any:
    """Zero EF residual pytree matching a stacked (n, ...) params tree."""
    if compressor is None:
        return ()
    return jax.tree.map(lambda l: jnp.zeros(l.shape, l.dtype), stacked)


# ---------------------------------------------------------------------------
# Error-feedback mixing wrappers (the engines' line-6 replacement)
# ---------------------------------------------------------------------------


def make_flat_ef_gossip(compressor: Compressor, mix_fn: Callable,
                        n_agents: int, *,
                        fused_int8_pallas: bool = False,
                        block_d: int | None = None) -> Callable:
    """Whole-buffer EF gossip: (w, p, res, key_c) -> (y, new_res).

    ``mix_fn(w, s) -> W @ s`` is the engine's resolved uncompressed mix
    (dense einsum / Pallas kernel / sparse gather) — it must apply the
    *full* W including the diagonal; the wrapper adds the
    ``diag(W)·(p − s)`` correction that swaps each agent's own compressed
    value back for its full-precision iterate.

    ``fused_int8_pallas=True`` (flat engine, ``gossip_impl='pallas'`` ×
    ``int8``) mixes straight from the int8 payload with the fused
    dequantize→mix→correct Pallas kernel (kernels/compress_mix.py) — the
    f32 dequantized buffer never touches HBM.  The quantization itself
    stays on the shared XLA codec so the emitted q is bit-identical to
    every other engine's (the fully-fused send-side ``quant_mix`` kernel
    can flip borderline stochastic roundings by one ulp of ``floor``
    relative to XLA's fusion, which would break the engines' exact
    cross-layout equivalence).
    """
    use_fused = fused_int8_pallas and compressor.name == "int8"

    def gossip(w: jax.Array, p: jax.Array, res: jax.Array,
               key_c: jax.Array):
        keys = jax.random.split(key_c, n_agents) if compressor.needs_key \
            else None
        u = p + res
        payload = compressor.encode(keys, u)
        s = compressor.decode(payload, u.dtype, u.shape[1])
        if use_fused:
            from repro.kernels import ops as kernel_ops
            kw = {} if block_d is None else {"block_d": block_d}
            y = kernel_ops.dequant_mix(w, payload["q"], payload["scale"],
                                       p, **kw)
            return y.astype(p.dtype), u - s
        diag = jnp.diagonal(w).astype(p.dtype)[:, None]
        y = mix_fn(w, s) + diag * (p - s)
        return y, u - s

    return gossip


def make_tree_ef_gossip(compressor: Compressor, gossip_fn: Callable,
                        n_agents: int) -> Callable:
    """Leaf-wise EF gossip for the tree engine: (w, p_tree, res_tree, key_c)
    -> (y_tree, new_res_tree).

    Each leaf is compressed independently (reshaped to (n, d_leaf)), so the
    int8 per-row scales are per-*leaf*-row — coarser-grained than the flat
    engine's whole-row scales.  Compressed tree and flat trajectories
    therefore differ (uncompressed ones stay identical); the flat layout is
    the hot path, the tree path exists so compression composes with every
    engine.  Per-leaf noise keys are decorrelated with fold_in(key_c, leaf).
    """

    def gossip(w: jax.Array, p_tree: Any, res_tree: Any, key_c: jax.Array):
        leaves_p, treedef = jax.tree.flatten(p_tree)
        leaves_r = treedef.flatten_up_to(res_tree)
        s_leaves, new_res = [], []
        for li, (pl, rl) in enumerate(zip(leaves_p, leaves_r)):
            n = pl.shape[0]
            u = (pl + rl).reshape(n, -1)
            keys = jax.random.split(jax.random.fold_in(key_c, li), n) \
                if compressor.needs_key else None
            s = compressor.decode(compressor.encode(keys, u), u.dtype,
                                  u.shape[1])
            s_leaves.append(s.reshape(pl.shape))
            new_res.append((u - s).reshape(pl.shape))
        s_tree = jax.tree.unflatten(treedef, s_leaves)
        y_tree = gossip_fn(w, s_tree)
        diag = jnp.diagonal(w)

        def correct(y, pl, sl):
            dg = diag.astype(pl.dtype)[(...,) + (None,) * (pl.ndim - 1)]
            return y + dg * (pl - sl)

        y_tree = jax.tree.map(correct, y_tree, p_tree, s_tree)
        return y_tree, jax.tree.unflatten(treedef, new_res)

    return gossip
