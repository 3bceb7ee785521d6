"""Delta-parameterized agent state: shared base (D,) + per-agent deltas.

FedDec's convergence story is that gossip keeps the per-agent iterates
clustered around one shared trajectory — the paper bounds exactly this
consensus deviation ||x_i − x̄||, shrinking as network connectivity grows.
This module makes that bound the *representation*: instead of a dense
(n_agents, D) buffer, each agent is ``base (D,) + delta_i`` where delta_i is
stored/communicated in a compressed form whose size tracks the deviation the
algorithm already pays to keep small.

A ``DeltaSpec`` picks the delta family:

  * ``full``       — exact two-term delta (p_i, c_i): lossless and
    **bit-exact** (see below), 2·D·b bytes/row.  The conformance anchor,
    not a compression: the delta engine at rank=full must reproduce the
    flat engine's trajectory bit-for-bit (the PR 4/5/6 gate).
  * ``topk:K``     — keep the K largest-|delta| entries per agent
    (values + int32 indices): K·(b + 4) bytes/row.
  * ``lowrank:R``  — reshape delta_i to a (d1, d2) matrix (d1·d2 = D,
    near-square factorization) and keep its rank-R truncated SVD
    U_i V_i: R·(d1 + d2)·b bytes/row.

The codecs implement the :class:`repro.core.compress.Compressor` interface
(each instance closes over the shared ``base`` row), so the flat engine's
error-feedback gossip wrapper (``compress.make_flat_ef_gossip``) reuses
them unchanged: the wire carries the **encoded delta payload**, the EF
residual absorbs the truncation error, and with the ``full`` codec the
residual is exactly zero every step.

Bit-exactness of the ``full`` codec (round-to-nearest IEEE arithmetic):
``encode`` stores p = fl(x − base) plus the compensation term
c = fl(x − fl(base + p)); ``decode`` recomputes fl(fl(base + p) + c).
fl(base + p) agrees with x to within a couple of ulps of the larger
operand, so by Sterbenz's lemma the subtraction x − fl(base + p) is exact
(c carries no rounding error) and the final addition reproduces x exactly
— ``decode(encode(x)) == x`` bitwise, property-tested over adversarial
magnitudes in tests/test_delta.py.  With s == u bitwise the EF correction
term diag(W)·(p − s) is exactly zero and the gossip reduces to the
uncompressed mix — the same argument that made the identity codec
bit-identical in PR 4.

:class:`DeltaStore` is the host-resident population counterpart of
``population.PopulationStore``: same gather/scatter/ages surface, but the
file-backed payload is the encoded delta (numpy mirror of the codecs), so
the 1e6-agent host store shrinks from O(n_total·D) to O(n_total·K) bytes.

Cost model: :func:`repro.launch.analysis.delta_cost_model` (jax-free
mirror of :func:`delta_store_bytes_per_row`).
"""

from __future__ import annotations

import dataclasses
import json
import os
import tempfile

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import compress as compress_lib

__all__ = ["DeltaSpec", "parse_delta", "DELTA_CHOICES", "factor_dims",
           "delta_store_bytes_per_row", "make_delta_codec",
           "FullDeltaCodec", "TopKDeltaCodec", "LowRankDeltaCodec",
           "DeltaStore"]

# canonical spellings for CLI help; K/R are positive integer counts/ranks
DELTA_CHOICES = ("none", "full", "topk:K", "lowrank:R")


@dataclasses.dataclass(frozen=True)
class DeltaSpec:
    """Validated delta parameterization: kind + rank/sparsity budget.

    ``rank`` is the kept-entry count K for 'topk' and the SVD rank R for
    'lowrank'; 0 (unused) for 'none'/'full'.
    """

    kind: str = "none"
    rank: int = 0

    def __post_init__(self):
        if self.kind not in ("none", "full", "topk", "lowrank"):
            raise ValueError(f"unknown delta kind {self.kind!r}")
        if self.kind in ("topk", "lowrank") and self.rank < 1:
            raise ValueError(
                f"delta {self.kind!r} needs a positive rank, "
                f"got {self.rank}")

    @property
    def is_lossless(self) -> bool:
        return self.kind in ("none", "full")

    @property
    def spec_str(self) -> str:
        if self.kind in ("none", "full"):
            return self.kind
        return f"{self.kind}:{self.rank}"


def parse_delta(spec: str) -> DeltaSpec:
    """'none' | 'full' | 'topk:K' | 'lowrank:R' → DeltaSpec."""
    if spec in ("none", "full"):
        return DeltaSpec(kind=spec)
    for kind in ("topk", "lowrank"):
        if spec.startswith(kind + ":"):
            try:
                rank = int(spec[len(kind) + 1:])
            except ValueError:
                rank = -1
            return DeltaSpec(kind=kind, rank=rank)  # validates rank >= 1
    raise ValueError(f"unknown delta spec {spec!r}; choose from "
                     f"{'|'.join(DELTA_CHOICES)}")


def factor_dims(d: int) -> tuple[int, int]:
    """Near-square (d1, d2) with d1·d2 = d, d1 <= d2 (lowrank reshape).

    d1 is the largest divisor of d not exceeding sqrt(d); a prime d
    degenerates to (1, d) — rank-R then stores R·(1 + d) values, i.e. no
    saving, which the cost model makes visible rather than hiding.
    """
    d1 = 1
    f = 1
    while f * f <= d:
        if d % f == 0:
            d1 = f
        f += 1
    return d1, d // d1


def delta_store_bytes_per_row(spec: DeltaSpec, d: int,
                              param_bytes: int = 4) -> float:
    """Analytic per-agent payload bytes of the delta representation.

    Matches the wire bytes of the corresponding codec and the on-disk row
    of :class:`DeltaStore` (excluding the shared base and the per-agent
    staleness counter, which every store layout carries identically).
    """
    if spec.kind == "none":
        return float(d * param_bytes)
    if spec.kind == "full":
        return float(2 * d * param_bytes)
    if spec.kind == "topk":
        return float(min(spec.rank, d)) * (param_bytes + 4.0)
    d1, d2 = factor_dims(d)
    r = min(spec.rank, d1)
    return float(r * (d1 + d2) * param_bytes)


# ---------------------------------------------------------------------------
# Delta codecs (Compressor interface; each closes over the shared base row)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True, eq=False)
class FullDeltaCodec(compress_lib.Compressor):
    """Exact two-term delta: payload (p, c) with decode == x bitwise.

    p = fl(x − base) alone is *not* lossless (the subtraction rounds), so a
    compensation term c = fl(x − fl(base + p)) rides along; decode replays
    the identical op order fl(fl(base + p) + c).  2·D·b bytes/row — this is
    the bit-identity anchor of the delta engine, not a compression.
    """

    name: str = "delta_full"
    base: jax.Array | None = None

    def encode(self, keys, u):
        b = self.base[None, :].astype(u.dtype)
        p = u - b
        c = u - (b + p)
        return {"p": p, "c": c}

    def decode(self, payload, dtype, d=None):
        b = self.base[None, :].astype(dtype)
        return ((b + payload["p"].astype(dtype))
                + payload["c"].astype(dtype))

    def wire_bytes_per_row(self, d, param_bytes=4):
        return float(2 * d * param_bytes)


@dataclasses.dataclass(frozen=True, eq=False)
class TopKDeltaCodec(compress_lib.Compressor):
    """Top-k sparse delta: keep the K largest-|x − base| entries per agent.

    Deterministic (lax.top_k ties break by index); the wire carries kept
    delta values + int32 column indices, K·(b + 4) bytes/row.  The dropped
    delta mass lands in the EF residual.
    """

    name: str = "delta_topk"
    base: jax.Array | None = None
    k: int = 1

    def k_of(self, d: int) -> int:
        return max(1, min(d, self.k))

    def encode(self, keys, u):
        delta = u - self.base[None, :].astype(u.dtype)
        k = self.k_of(u.shape[1])
        _, idx = jax.lax.top_k(jnp.abs(delta.astype(jnp.float32)), k)
        vals = jnp.take_along_axis(delta, idx, axis=1)
        return {"v": vals, "i": idx.astype(jnp.int32)}

    def decode(self, payload, dtype, d=None):
        assert d is not None, "top-k delta decode needs the row width d"
        vals, idx = payload["v"], payload["i"]
        n = vals.shape[0]
        rows = jnp.arange(n, dtype=jnp.int32)[:, None]
        sparse = jnp.zeros((n, d), dtype).at[rows, idx].set(
            vals.astype(dtype))
        return self.base[None, :].astype(dtype) + sparse

    def wire_bytes_per_row(self, d, param_bytes=4):
        return float(self.k_of(d)) * (param_bytes + 4.0)


@dataclasses.dataclass(frozen=True, eq=False)
class LowRankDeltaCodec(compress_lib.Compressor):
    """Low-rank delta: truncated SVD of the (d1, d2)-reshaped delta row.

    Payload is (U_i Σ_i, V_i) per agent — R·(d1 + d2)·b bytes/row, the
    best rank-R approximation in Frobenius norm; the truncated spectrum
    lands in the EF residual.
    """

    name: str = "delta_lowrank"
    base: jax.Array | None = None
    rank: int = 1

    def _dims(self, d: int) -> tuple[int, int, int]:
        d1, d2 = factor_dims(d)
        return d1, d2, min(self.rank, d1)

    def encode(self, keys, u):
        d = u.shape[1]
        d1, d2, r = self._dims(d)
        delta = (u - self.base[None, :].astype(u.dtype))
        m = delta.astype(jnp.float32).reshape(u.shape[0], d1, d2)
        uu, s, vt = jnp.linalg.svd(m, full_matrices=False)
        return {"u": uu[:, :, :r] * s[:, None, :r], "v": vt[:, :r, :]}

    def decode(self, payload, dtype, d=None):
        assert d is not None, "low-rank delta decode needs the row width d"
        lowrank = jnp.einsum("nir,nrj->nij", payload["u"], payload["v"])
        delta = lowrank.reshape(lowrank.shape[0], -1).astype(dtype)
        return self.base[None, :].astype(dtype) + delta

    def wire_bytes_per_row(self, d, param_bytes=4):
        d1, d2, r = self._dims(d)
        return float(r * (d1 + d2) * param_bytes)


def make_delta_codec(spec: DeltaSpec | str,
                     base: jax.Array) -> compress_lib.Compressor | None:
    """DeltaSpec (or spec string) + base row → Compressor; None for 'none'."""
    if isinstance(spec, str):
        spec = parse_delta(spec)
    base = jnp.asarray(base).reshape(-1)
    if spec.kind == "none":
        return None
    if spec.kind == "full":
        return FullDeltaCodec(base=base)
    if spec.kind == "topk":
        return TopKDeltaCodec(base=base, k=spec.rank)
    return LowRankDeltaCodec(base=base, rank=spec.rank)


# ---------------------------------------------------------------------------
# Host-resident delta store (the population engine's O(n_total·K) backend)
# ---------------------------------------------------------------------------


def _np_topk_encode(rows: np.ndarray, base: np.ndarray, k: int):
    """Numpy mirror of TopKDeltaCodec.encode (stable = lax.top_k tie order)."""
    delta = rows - base[None, :]
    order = np.argsort(-np.abs(delta.astype(np.float32)), axis=1,
                       kind="stable")
    idx = order[:, :k].astype(np.int32)
    vals = np.take_along_axis(delta, idx, axis=1)
    return vals, idx


class DeltaStore:
    """Host delta store: base (D,) + per-agent encoded payload memmaps.

    Drop-in for :class:`population.PopulationStore` (same n_total / d /
    last_round / ages / gather / scatter surface) with the dense
    (n_total, D) rows replaced by the DeltaSpec's payload:

      * ``full``       — p + c memmaps (n_total, D) each: the lossless
        anchor (gather∘scatter is bitwise identity), 2× flat bytes;
      * ``topk:K``     — (n_total, K) f32 values + (n_total, K) int32
        indices: the O(n_total·K) store the million-agent engine wants;
      * ``lowrank:R``  — (n_total, d1, R) + (n_total, R, d2) factors.

    ``gather`` decodes to dense cohort rows (what the device round
    consumes); ``scatter`` re-encodes — for lossy kinds the truncation is
    the storage compression (the per-round training residual is already
    carried on-device by the EF gossip; the store projection composes with
    it as a second, per-writeback truncation).
    """

    def __init__(self, spec: DeltaSpec, base: np.ndarray, payload: dict,
                 last_round: np.ndarray, path: str | None = None):
        self.spec = spec
        self.base = np.asarray(base).reshape(-1)
        self.payload = payload
        self.last_round = np.asarray(last_round, dtype=np.int64)
        self.path = path
        n = self.last_round.shape[0]
        for name, arr in payload.items():
            if arr.shape[0] != n:
                raise ValueError(f"payload[{name!r}] has leading dim "
                                 f"{arr.shape[0]}, expected {n}")

    @property
    def n_total(self) -> int:
        return self.last_round.shape[0]

    @property
    def d(self) -> int:
        return self.base.shape[0]

    @property
    def nbytes(self) -> int:
        """Live host bytes: base + payload memmaps + staleness counters."""
        return int(self.base.nbytes + self.last_round.nbytes
                   + sum(a.nbytes for a in self.payload.values()))

    # -- construction -------------------------------------------------------

    @classmethod
    def create(cls, n_total: int, row_init: np.ndarray,
               spec: DeltaSpec | str, path: str | None = None,
               dtype=np.float32, chunk_rows: int = 65536) -> "DeltaStore":
        """z_i^1 = z^1 ∀i (Alg. 1 line 1): base = z^1, every delta = 0.

        ``path=None`` backs the payload with unlinked temp files (handles
        kept alive on the arrays), matching PopulationStore.create; a real
        ``path`` is used as a filename prefix (one file per payload leaf).
        """
        if isinstance(spec, str):
            spec = parse_delta(spec)
        if spec.kind == "none":
            raise ValueError("DeltaStore needs a non-'none' DeltaSpec; use "
                             "PopulationStore for the dense layout")
        base = np.asarray(row_init, dtype=dtype).reshape(-1)
        d = base.shape[0]

        def _memmap(name, shape, mdtype):
            if path is None:
                f = tempfile.NamedTemporaryFile(
                    prefix=f"delta_{name}_", suffix=".payload")
                arr = np.memmap(f, dtype=mdtype, mode="w+", shape=shape)
                arr._tmpfile = f  # keep the unlinked handle alive
            else:
                arr = np.memmap(f"{path}.{name}", dtype=mdtype, mode="w+",
                                shape=shape)
            return arr

        if spec.kind == "full":
            payload = {"p": _memmap("p", (n_total, d), dtype),
                       "c": _memmap("c", (n_total, d), dtype)}
        elif spec.kind == "topk":
            k = min(spec.rank, d)
            payload = {"v": _memmap("v", (n_total, k), dtype),
                       "i": _memmap("i", (n_total, k), np.int32)}
        else:
            d1, d2 = factor_dims(d)
            r = min(spec.rank, d1)
            payload = {"u": _memmap("u", (n_total, d1, r), dtype),
                       "v": _memmap("v", (n_total, r, d2), dtype)}
        # zero delta encodes to all-zero payloads for every kind — chunked
        # writes only to keep peak RSS flat on sparse filesystems
        for arr in payload.values():
            for lo in range(0, n_total, chunk_rows):
                arr[lo:lo + chunk_rows] = 0
        last_round = np.full((n_total,), -1, dtype=np.int64)
        return cls(spec, base, payload, last_round, path=path)

    # -- the PopulationEngine surface ---------------------------------------

    def gather(self, ids: np.ndarray) -> np.ndarray:
        """Decode cohort ids to dense rows (the h2d upload payload)."""
        ids = np.asarray(ids)
        if self.spec.kind == "full":
            p = np.array(self.payload["p"][ids])
            c = np.array(self.payload["c"][ids])
            # identical op order to FullDeltaCodec.decode → bitwise equal
            return (self.base[None, :] + p) + c
        if self.spec.kind == "topk":
            vals = np.array(self.payload["v"][ids])
            idx = np.array(self.payload["i"][ids])
            rows = np.tile(self.base[None, :], (ids.shape[0], 1))
            np.put_along_axis(rows, idx,
                              np.take_along_axis(rows, idx, axis=1) + vals,
                              axis=1)
            return rows
        u = np.array(self.payload["u"][ids])
        v = np.array(self.payload["v"][ids])
        delta = np.einsum("nir,nrj->nij", u, v).reshape(ids.shape[0], -1)
        return self.base[None, :] + delta.astype(self.base.dtype)

    def scatter(self, ids: np.ndarray, values: np.ndarray) -> None:
        """Encode a finished cohort back into the payload memmaps."""
        ids = np.asarray(ids)
        values = np.asarray(values, dtype=self.base.dtype)
        if self.spec.kind == "full":
            p = values - self.base[None, :]
            c = values - (self.base[None, :] + p)
            self.payload["p"][ids] = p
            self.payload["c"][ids] = c
            return
        if self.spec.kind == "topk":
            k = self.payload["v"].shape[1]
            vals, idx = _np_topk_encode(values, self.base, k)
            self.payload["v"][ids] = vals
            self.payload["i"][ids] = idx
            return
        d1 = self.payload["u"].shape[1]
        r = self.payload["u"].shape[2]
        m = (values - self.base[None, :]).astype(np.float32)
        m = m.reshape(values.shape[0], d1, -1)
        uu, s, vt = np.linalg.svd(m, full_matrices=False)
        self.payload["u"][ids] = uu[:, :, :r] * s[:, None, :r]
        self.payload["v"][ids] = vt[:, :r, :]

    def ages(self, ids: np.ndarray, round_idx: int) -> np.ndarray:
        """Participation age (rounds since last scheduled; never < 0)."""
        return np.maximum(
            round_idx - self.last_round[np.asarray(ids)], 0)

    # -- checkpointing (chunked; one .npy per payload leaf) -----------------

    def save(self, directory: str, step: int) -> str:
        out = os.path.join(directory, f"deltapop_{step:08d}")
        os.makedirs(out, exist_ok=True)
        np.save(os.path.join(out, "base.npy"), self.base)
        np.save(os.path.join(out, "last_round.npy"), self.last_round)
        chunk = 65536
        for name, arr in self.payload.items():
            dst = np.lib.format.open_memmap(
                os.path.join(out, f"payload_{name}.npy"), mode="w+",
                dtype=arr.dtype, shape=arr.shape)
            for lo in range(0, arr.shape[0], chunk):
                dst[lo:lo + chunk] = arr[lo:lo + chunk]
            dst.flush()
        meta = {"kind": self.spec.kind, "rank": self.spec.rank,
                "n_total": self.n_total, "d": self.d, "step": step}
        with open(os.path.join(out, "meta.json"), "w") as f:
            json.dump(meta, f)
        return out

    @classmethod
    def restore(cls, directory: str, step: int | None = None, *,
                writable_path: str | None = None) -> "DeltaStore":
        if step is None:
            snaps = sorted(p for p in os.listdir(directory)
                           if p.startswith("deltapop_"))
            if not snaps:
                raise FileNotFoundError(
                    f"no deltapop_* checkpoints under {directory}")
            src = os.path.join(directory, snaps[-1])
        else:
            src = os.path.join(directory, f"deltapop_{step:08d}")
        with open(os.path.join(src, "meta.json")) as f:
            meta = json.load(f)
        spec = DeltaSpec(kind=meta["kind"], rank=meta["rank"])
        base = np.load(os.path.join(src, "base.npy"))
        store = cls.create(meta["n_total"], base, spec, path=writable_path,
                           dtype=base.dtype)
        chunk = 65536
        for name, arr in store.payload.items():
            saved = np.load(os.path.join(src, f"payload_{name}.npy"),
                            mmap_mode="r")
            for lo in range(0, arr.shape[0], chunk):
                arr[lo:lo + chunk] = saved[lo:lo + chunk]
        store.last_round[:] = np.load(os.path.join(src, "last_round.npy"))
        return store
