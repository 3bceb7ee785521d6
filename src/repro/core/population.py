"""Million-agent population engine: cohort-sampled FedDec with streaming.

The paper's setting already assumes *partial participation* — each server
round touches only K sampled agents (Alg. 1 line 8) — yet every engine so
far materializes the full ``(n_agents, D)`` buffer on device, capping n at
~1024.  This module adds the population layer that makes
``n_total ≫ n_active`` first-class:

* the **population store** lives on the host as an ``np.memmap``-backed
  ``(n_total, D)`` row file (+ per-agent last-participation counters), so
  n_total = 1e6 never materializes whole on device *or* in host RAM;
* each round samples a **cohort** of ``cohort_size`` agent ids (uniform /
  weighted / stale-prioritized), streams their rows host→device, runs the
  existing fused Algorithm-1 round (repro.core.engine.build_step_body — the
  same scan body every other engine runs) on the cohort buffer, and writes
  the rows back;
* mixing is rebuilt **sparse-only on the sampled subgraph** every round
  (:func:`repro.core.topology.induced_subgraph` + CSR reindex — never a
  dense (n_total, n_total) W): Metropolis weights stay doubly stochastic on
  any subgraph (topology.metropolis_weights), optionally tilted by
  per-agent participation age (FedPAE-style,
  :func:`repro.core.mixing.staleness_tilted_weights`);
* uploads and write-backs are **double-buffered** over JAX's async
  dispatch: while round r executes on device, round r+1's cohort is
  sampled, gathered, reindexed and ``jax.device_put`` — and round r−1's
  output is scattered back.  A conflict check drains the pipeline whenever
  consecutive cohorts intersect, so the overlapped schedule is *semantically
  identical* to the synchronous one (tested) — with n_total ≫ cohort the
  collision probability is ~cohort²/n_total and the pipeline stays full.

Peak device memory is bounded by the cohort — two (cohort, D) buffers plus
two cohort-sized ELL edge tables — **independent of n_total**
(``launch/analysis.population_cost_model``).

Bit-identity: with ``n_total == cohort_size`` and uniform sampling the
cohort is the identity slice every round, the induced subgraph is the full
graph, and the ELL tables match ``gossip.make_sparse_gossip`` entry-for-
entry — the population trajectory is then **bit-identical** to the flat
engine with ``gossip_impl='sparse'`` (tested + pinned in the benchmark
acceptance).
"""

from __future__ import annotations

import dataclasses
import tempfile
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import engine
from repro.core import flat as flat_lib
from repro.core import mixing as mixing_lib
from repro.core import server as server_lib
from repro.core import topology as topo
from repro.core.feddec import FedDecConfig
from repro.core.flat import FlatFedState, FlatSpec

__all__ = ["SAMPLINGS", "PopulationSpec", "PopulationStore", "CohortMix",
           "sample_cohort", "build_cohort_mix", "make_cohort_round",
           "PopulationEngine"]

SAMPLINGS = ("uniform", "weighted", "stale")

GradFn = Callable[[Any, Any, jax.Array], tuple[jax.Array, Any]]
LrFn = Callable[[jax.Array], jax.Array]


# ---------------------------------------------------------------------------
# Spec
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PopulationSpec:
    """Static configuration of the population layer.

    Attributes:
      n_total: population size (agents in the host store).
      cohort_size: agents streamed + trained per round (n_active).
      sampling: cohort sampler — 'uniform' (without replacement),
        'weighted' (∝ engine-supplied per-agent weights), or 'stale'
        (∝ 1 + participation age, prioritizing left-out agents).
      staleness: FedPAE age-tilt β for the cohort mixing matrix; 0 keeps
        plain (doubly stochastic) Metropolis weights, bit-exactly.
      max_degree: static ELL width of the per-round cohort mix tables
        (compiled once; cohort subgraphs whose degree exceeds it raise).
      n_clusters: > 1 enables the two-tier hierarchical server round:
        edge-cluster averaging (contiguous id blocks) before the K-sample
        server aggregation.  0/1 = the paper's flat server round.
      seed: host-side RNG seed for cohort sampling.
    """

    n_total: int
    cohort_size: int
    sampling: str = "uniform"
    staleness: float = 0.0
    max_degree: int = 8
    n_clusters: int = 0
    seed: int = 0

    def __post_init__(self):
        if self.n_total < 1:
            raise ValueError(f"n_total must be ≥ 1, got {self.n_total}")
        if not 1 <= self.cohort_size <= self.n_total:
            raise ValueError(
                f"cohort_size must be in [1, n_total={self.n_total}], "
                f"got {self.cohort_size}")
        if self.sampling not in SAMPLINGS:
            raise ValueError(f"unknown sampling {self.sampling!r}; choose "
                             f"from {'|'.join(SAMPLINGS)}")
        if self.staleness < 0.0:
            raise ValueError(f"staleness must be ≥ 0, got {self.staleness}")
        if self.max_degree < 1:
            raise ValueError(f"max_degree must be ≥ 1, got {self.max_degree}")
        if self.n_clusters > self.cohort_size:
            raise ValueError(
                f"n_clusters ({self.n_clusters}) cannot exceed cohort_size "
                f"({self.cohort_size})")

    def cluster_of(self, ids: np.ndarray) -> np.ndarray:
        """Contiguous-block edge-cluster assignment of population ids."""
        m = max(self.n_clusters, 1)
        return ((np.asarray(ids, dtype=np.int64) * m)
                // self.n_total).astype(np.int32)


# ---------------------------------------------------------------------------
# Host-side population store (memmap; n_total never on device whole)
# ---------------------------------------------------------------------------


class PopulationStore:
    """(n_total, D) host row store + per-agent last-participation round.

    ``rows[i]`` is Algorithm 1's z_i for population agent i, held in a
    file-backed ``np.memmap`` so only gathered cohort slices ever occupy
    process memory; ``last_round[i]`` is the last round agent i was
    scheduled into (−1 = never), driving the 'stale' sampler and the
    FedPAE age tilt.
    """

    def __init__(self, rows: np.ndarray, last_round: np.ndarray,
                 path: str | None = None):
        rows = np.asarray(rows) if not isinstance(rows, np.memmap) else rows
        if rows.ndim != 2:
            raise ValueError(f"rows must be (n_total, D), got {rows.shape}")
        if last_round.shape != (rows.shape[0],):
            raise ValueError(
                f"last_round must be ({rows.shape[0]},), "
                f"got {last_round.shape}")
        self.rows = rows
        self.last_round = np.asarray(last_round, dtype=np.int64)
        self.path = path

    @property
    def n_total(self) -> int:
        return self.rows.shape[0]

    @property
    def d(self) -> int:
        return self.rows.shape[1]

    @property
    def nbytes(self) -> int:
        """Live host bytes: dense rows + staleness counters."""
        return int(self.rows.nbytes + self.last_round.nbytes)

    @classmethod
    def create(cls, n_total: int, row_init: np.ndarray,
               path: str | None = None, dtype=np.float32,
               chunk_rows: int = 65536) -> "PopulationStore":
        """z_i^1 = z^1 ∀i (Alg. 1 line 1) as a memmap, written in chunks.

        ``path=None`` backs the store with an unlinked temp file (memmap
        kept alive by the open handle), so even scratch runs never hold
        (n_total, D) in RAM.
        """
        row = np.asarray(row_init, dtype=dtype).reshape(-1)
        d = row.shape[0]
        if path is None:
            f = tempfile.NamedTemporaryFile(
                prefix="population_", suffix=".rows")
            rows = np.memmap(f, dtype=dtype, mode="w+", shape=(n_total, d))
            rows._tmpfile = f  # keep the unlinked handle alive
        else:
            rows = np.memmap(path, dtype=dtype, mode="w+",
                             shape=(n_total, d))
        for lo in range(0, n_total, chunk_rows):
            hi = min(lo + chunk_rows, n_total)
            rows[lo:hi] = row[None, :]
        last_round = np.full((n_total,), -1, dtype=np.int64)
        return cls(rows, last_round, path=path)

    def gather(self, ids: np.ndarray) -> np.ndarray:
        """Cohort rows (copy) — the host side of the h2d upload."""
        return np.array(self.rows[np.asarray(ids)])

    def scatter(self, ids: np.ndarray, values: np.ndarray) -> None:
        """Write a finished cohort back (the d2h side)."""
        self.rows[np.asarray(ids)] = np.asarray(
            values, dtype=self.rows.dtype)

    def ages(self, ids: np.ndarray, round_idx: int) -> np.ndarray:
        """Participation age (rounds since last scheduled; never < 0)."""
        return np.maximum(
            round_idx - self.last_round[np.asarray(ids)], 0)

    # -- checkpointing (chunked; see repro.checkpoint) ----------------------

    def save(self, directory: str, step: int) -> str:
        """Chunk-stream rows + staleness counters to ``pop_<step>/``."""
        from repro.checkpoint import save_population
        return save_population(directory, step, self.rows, self.last_round)

    @classmethod
    def restore(cls, directory: str, step: int | None = None, *,
                writable_path: str | None = None) -> "PopulationStore":
        """Rebuild a store from a checkpoint (latest when ``step=None``).

        By default the restored rows are copied into a fresh (writable)
        temp-file memmap; pass ``writable_path`` to place the live store
        file explicitly.
        """
        from repro.checkpoint import load_population
        rows, last_round, meta = load_population(directory, step)
        store = cls.create(meta["n_total"], np.zeros(meta["d"], rows.dtype),
                           path=writable_path, dtype=rows.dtype)
        chunk = 65536
        for lo in range(0, meta["n_total"], chunk):
            store.rows[lo:lo + chunk] = rows[lo:lo + chunk]
        store.last_round[:] = last_round
        return store


# ---------------------------------------------------------------------------
# Cohort sampling (host-side, numpy RNG)
# ---------------------------------------------------------------------------


def sample_cohort(rng: np.random.Generator, spec: PopulationSpec,
                  last_round: np.ndarray, round_idx: int,
                  weights: np.ndarray | None = None) -> np.ndarray:
    """Draw one round's cohort ids, **sorted ascending**.

    Sorted order gives memmap gather locality and makes the
    n_total == cohort_size uniform cohort the identity slice — the
    bit-identity anchor against the flat engine.

    'weighted' / 'stale' use Gumbel top-k (one O(n_total) vectorized pass)
    — exact sampling without replacement ∝ the weight vector.
    """
    n, c = spec.n_total, spec.cohort_size
    if spec.sampling == "uniform":
        ids = rng.choice(n, size=c, replace=False)
    else:
        if spec.sampling == "weighted":
            if weights is None:
                raise ValueError(
                    "sampling='weighted' needs a per-agent weights vector")
            w = np.asarray(weights, dtype=np.float64)
            if w.shape != (n,) or np.any(w < 0) or w.sum() <= 0:
                raise ValueError(
                    f"weights must be (n_total,) ≥ 0 with a positive sum, "
                    f"got shape {w.shape}")
        else:  # 'stale': prioritize agents longest out of a cohort
            w = 1.0 + np.maximum(round_idx - last_round, 0).astype(np.float64)
        with np.errstate(divide="ignore"):
            gumbel = np.log(w) + rng.gumbel(size=n)
        ids = np.argpartition(-gumbel, c - 1)[:c]
    return np.sort(ids).astype(np.int64)


# ---------------------------------------------------------------------------
# Per-round cohort mix tables (sparse-only subgraph Metropolis)
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class CohortMix:
    """Traced per-round mixing tables of one cohort (static ELL shapes).

    The same padded-neighbour-list layout as ``gossip.make_sparse_gossip``'s
    ELL path — padding slots point at the row's own agent with weight 0, so
    they contribute exact +0.0 and every round reuses one compiled program.
    """

    nbr: jax.Array      # (c, max_degree) int32 — padding = own row
    wv: jax.Array       # (c, max_degree) f32   — padding = 0.0
    diag: jax.Array     # (c,) f32
    cluster: jax.Array  # (c,) int32 — hierarchical tier-1 assignment


def build_cohort_mix(graph: "topo.SparseGraph | topo.Graph",
                     ids: np.ndarray, spec: PopulationSpec,
                     ages: np.ndarray | None = None,
                     dtype=np.float32) -> CohortMix:
    """Metropolis mixing on the induced cohort subgraph, as ELL tables.

    Host-side numpy (runs inside the streaming pipeline, overlapped with
    device compute).  Never touches a dense (n_total, n_total) array: the
    subgraph comes from :func:`topology.induced_subgraph` (CSR reindex) and
    only the (c, c) cohort W is densified.  ``spec.staleness > 0`` applies
    the FedPAE age tilt before the tables are extracted.
    """
    sub = topo.induced_subgraph(graph, ids)
    c = sub.n
    max_deg_actual = int(sub.degrees.max()) if c else 0
    if max_deg_actual > spec.max_degree:
        raise ValueError(
            f"cohort subgraph degree {max_deg_actual} exceeds the static "
            f"ELL width max_degree={spec.max_degree}; raise "
            f"PopulationSpec.max_degree (graph family bound)")
    w = topo.metropolis_weights(sub)
    if spec.staleness > 0.0:
        if ages is None:
            raise ValueError("staleness > 0 needs per-cohort ages")
        w = mixing_lib.staleness_tilted_weights(w, ages, spec.staleness)

    adj = sub.adjacency
    nbr = np.tile(np.arange(c, dtype=np.int32)[:, None],
                  (1, spec.max_degree))
    wv = np.zeros((c, spec.max_degree), dtype=dtype)
    for i in range(c):
        js = np.flatnonzero(adj[i])
        nbr[i, :len(js)] = js
        wv[i, :len(js)] = w[i, js]
    diag = np.diagonal(w).astype(dtype)
    return CohortMix(nbr=jnp.asarray(nbr), wv=jnp.asarray(wv),
                     diag=jnp.asarray(diag),
                     cluster=jnp.asarray(spec.cluster_of(ids)))


def _ell_mix(mix: CohortMix, x: jax.Array) -> jax.Array:
    """The cohort gossip: same op sequence as gossip.make_sparse_gossip ELL.

    y_i = W_ii x_i + Σ_k wv[i,k]·x[nbr[i,k]] — padding slots add exact +0.0,
    and with max_degree == the graph's max degree the adds happen in the
    same order as the flat sparse engine's (the bit-identity anchor).
    """
    y = mix.diag.astype(x.dtype)[:, None] * x
    for k in range(mix.nbr.shape[1]):
        y = y + mix.wv[:, k].astype(x.dtype)[:, None] \
            * jnp.take(x, mix.nbr[:, k], axis=0)
    return y


# ---------------------------------------------------------------------------
# The cohort round executor (the engine.py scan body, per-round traced mix)
# ---------------------------------------------------------------------------


def make_cohort_round(spec: PopulationSpec, flat_spec: FlatSpec,
                      grad_fn: GradFn, lr_fn: LrFn, *, h: int, k: int,
                      server_enabled: bool = True, optimizer=None,
                      metrics_fn=None, jit: bool = True):
    """Lower ``round_fn(state, batches, key, mix)`` for one cohort.

    This is the flat engine's fused H-step round — the same
    ``engine.build_step_body`` vtable — with two ops swapped: ``sample_w``
    returns the *traced* per-round :class:`CohortMix` instead of a static
    W, and ``gossip`` is the ELL subgraph mix.  ``spec.n_clusters > 1``
    additionally swaps the server op for the two-tier hierarchical round
    (edge-cluster averaging → K-sample server).  Compiled once; every
    round re-runs it with fresh cohort tables.
    """
    c = spec.cohort_size
    # carrier config for the shared flat vtable: n_agents == cohort_size,
    # gossip_impl 'none' (the resolved gossip is replaced by the cohort mix)
    cfg = FedDecConfig(mixing=mixing_lib.identity_mixing(c), h=h, k=k,
                       server_enabled=server_enabled, gossip_impl="none")
    base = flat_lib._flat_ops(cfg, flat_spec, grad_fn, lr_fn, None,
                              optimizer)

    def hierarchical_server(mix: CohortMix):
        m = spec.n_clusters

        def do_round(args):
            key_server, x = args
            # tier 1: edge-cluster averaging inside the cohort
            ones = jnp.ones((c,), dtype=x.dtype)
            cnt = jax.ops.segment_sum(ones, mix.cluster, num_segments=m)
            sums = jax.ops.segment_sum(x, mix.cluster, num_segments=m)
            means = sums / jnp.maximum(cnt, 1.0)[:, None]
            x_cl = jnp.take(means, mix.cluster, axis=0)
            # tier 2: the paper's K-sample server round on the
            # cluster-averaged buffer
            return server_lib.server_round_flat(key_server, x_cl, k)

        def server(key_server, x_next, t):
            if not server_enabled:
                return x_next
            return jax.lax.cond((t + 1) % h == 0, do_round,
                                lambda args: args[1], (key_server, x_next))

        return server

    def round_fn(state: FlatFedState, batches, key, mix: CohortMix):
        ops = dataclasses.replace(
            base,
            sample_w=lambda key_w: mix,
            gossip=_ell_mix,
            server=hierarchical_server(mix) if spec.n_clusters > 1
            else base.server)
        step = engine.build_step_body(ops)
        return engine.make_scan_round(step, metrics_fn=metrics_fn)(
            state, batches, key)

    return engine.finalize_executor(round_fn, donate=True, jit=jit)


# ---------------------------------------------------------------------------
# The streaming driver (double-buffered host↔device pipeline)
# ---------------------------------------------------------------------------


class PopulationEngine:
    """Cohort-streamed FedDec over a host-resident population.

    Per round r the pipeline runs (overlap=True, the default):

      dispatch round r  →  [device executes asynchronously]
      writeback round r−1      (blocks only on r−1's — finished — output)
      sample cohort r+1; if it intersects cohort r, drain (correctness)
      gather + subgraph + device_put round r+1   (overlapped with r)

    JAX's async dispatch makes the jitted round and ``device_put`` return
    immediately, so the host-side stages (memmap gather/scatter, induced
    subgraph + Metropolis reindex, batch generation) hide under device
    compute.  ``overlap=False`` blocks after every stage — the synchronous
    baseline the benchmark compares against.  Both schedules produce
    identical trajectories (the conflict drain serializes exactly the
    rounds where overlap would read not-yet-written rows).
    """

    def __init__(self, spec: PopulationSpec, flat_spec: FlatSpec,
                 grad_fn: GradFn, lr_fn: LrFn,
                 graph: "topo.SparseGraph | topo.Graph", *, h: int, k: int,
                 server_enabled: bool = True, optimizer=None,
                 store: PopulationStore | None = None,
                 row_init: np.ndarray | None = None,
                 store_path: str | None = None,
                 delta: str = "none",
                 weights: np.ndarray | None = None, metrics_fn=None,
                 jit: bool = True):
        n = graph.n
        if n != spec.n_total:
            raise ValueError(
                f"graph has n={n} nodes but spec.n_total={spec.n_total}")
        if optimizer is not None:
            raise NotImplementedError(
                "population mode streams bare parameter rows (Algorithm 1's "
                "stateless SGD); per-agent optimizer state is not streamed")
        self.spec = spec
        self.flat_spec = flat_spec
        self.graph = graph if isinstance(graph, topo.SparseGraph) \
            else topo.csr_from_graph(graph)
        self.h, self.k = h, k
        self.weights = weights
        if store is None:
            if row_init is None:
                raise ValueError("pass either store= or row_init=")
            if delta != "none":
                # base = z^1, every agent row an encoded (initially zero)
                # delta: the host store is O(n_total·K) instead of
                # O(n_total·D) — see repro.core.delta.DeltaStore
                from repro.core.delta import DeltaStore
                store = DeltaStore.create(
                    spec.n_total,
                    np.asarray(row_init, dtype=flat_spec.dtype),
                    delta, path=store_path,
                    dtype=np.dtype(flat_spec.dtype))
            else:
                store = PopulationStore.create(
                    spec.n_total,
                    np.asarray(row_init, dtype=flat_spec.dtype),
                    path=store_path, dtype=np.dtype(flat_spec.dtype))
        elif delta != "none":
            from repro.core.delta import DeltaStore
            if not isinstance(store, DeltaStore):
                raise ValueError("delta != 'none' with an explicit store= "
                                 "needs a DeltaStore")
        if store.d != flat_spec.d:
            raise ValueError(f"store D={store.d} != flat spec D="
                             f"{flat_spec.d}")
        self.store = store
        self.round_idx = 0
        self.step = 1                     # the paper's t (starts at 1)
        self._rng = np.random.default_rng(spec.seed)
        self._round = make_cohort_round(
            spec, flat_spec, grad_fn, lr_fn, h=h, k=k,
            server_enabled=server_enabled, optimizer=optimizer,
            metrics_fn=metrics_fn, jit=jit)

    # -- pipeline stages ----------------------------------------------------

    def _sample(self) -> np.ndarray:
        """Cohort ids for round ``self.round_idx`` (the next unscheduled)."""
        return sample_cohort(self._rng, self.spec, self.store.last_round,
                             self.round_idx, self.weights)

    def _prepare(self, ids: np.ndarray, batch_fn, round_idx: int):
        """Host stage: gather rows, build subgraph tables, async upload."""
        ages = self.store.ages(ids, round_idx)
        mix = build_cohort_mix(self.graph, ids, self.spec, ages=ages,
                               dtype=np.dtype(self.flat_spec.dtype))
        rows = self.store.gather(ids)
        # mark participation at schedule time so the 'stale' sampler and the
        # age tilt see in-flight cohorts
        self.store.last_round[ids] = round_idx
        flat = jax.device_put(rows)          # async h2d, double buffer slot
        batches = batch_fn(round_idx, ids)
        return ids, flat, mix, batches

    def _writeback(self, ids: np.ndarray, new_state: FlatFedState,
                   metrics, out: list) -> None:
        """Host stage: blocks on this round's (usually finished) output."""
        self.store.scatter(ids, np.asarray(new_state.flat))
        out.append(jax.tree.map(np.asarray, metrics))

    # -- the driver ---------------------------------------------------------

    def run(self, n_rounds: int, batch_fn, key: jax.Array, *,
            overlap: bool = True) -> dict:
        """Run ``n_rounds`` fused H-step rounds over the population.

        Args:
          n_rounds: rounds to run (each is one compiled H-step scan).
          batch_fn: ``(round_idx, ids) -> batches`` with leading (H, c, ...)
            — the cohort's data stream (generated in the overlapped host
            stage, so data loading also hides under device compute).
          key: base PRNG key; per-step keys derive via fold_in(key, t)
            exactly like every other engine.

        Returns:
          dict of stacked per-round metrics (numpy, leading dim n_rounds)
          plus ``'drains'`` — how often the conflict check had to serialize.
        """
        if n_rounds < 1:
            return {"drains": 0}
        out: list = []
        drains = 0
        nxt = self._prepare(self._sample(), batch_fn, self.round_idx)
        pending = None
        for r in range(n_rounds):
            ids, flat, mix, batches = nxt
            state = FlatFedState(
                flat=flat, step=jnp.asarray(self.step, dtype=jnp.int32))
            new_state, metrics = self._round(state, batches, key, mix)
            if not overlap:
                jax.block_until_ready(new_state.flat)
            if pending is not None:
                self._writeback(*pending, out)   # round r−1 (finished)
                pending = None
            pending = (ids, new_state, metrics)
            self.step += self.h
            self.round_idx += 1
            if r + 1 < n_rounds:
                nxt_ids = self._sample()
                if np.intersect1d(nxt_ids, ids,
                                  assume_unique=True).size:
                    # pipeline hazard: next cohort reads rows still in
                    # flight — drain before gathering
                    self._writeback(*pending, out)
                    pending = None
                    drains += 1
                nxt = self._prepare(nxt_ids, batch_fn, self.round_idx)
        if pending is not None:
            self._writeback(*pending, out)
        stacked = jax.tree.map(lambda *xs: np.stack(xs), *out)
        stacked["drains"] = drains
        return stacked
