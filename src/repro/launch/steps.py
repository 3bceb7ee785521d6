"""The federated set-up of a training run: mixing graph, FedDec config and
the per-run configs of a sweep lattice.

``launch/train.py:train_loop`` and the on-chip benchmark build their round
executors from these; the executors themselves live in ``repro.core``.
"""

from __future__ import annotations

import dataclasses

from repro import sharding as shd
from repro.configs.base import ArchConfig, FedConfig
from repro.core import feddec, topology as topo
from repro.core.mixing import MixingDistribution

__all__ = ["build_fed_setup", "sweep_lattice_configs"]


def build_fed_setup(cfg: ArchConfig, axes: shd.MeshAxes,
                    fed: FedConfig | None = None):
    """(FedDecConfig, n_agents) for this arch on this mesh."""
    n = shd.n_agents_for(cfg, axes)
    fed = fed or FedConfig()
    if fed.graph.startswith("ring"):
        k = int(fed.graph[4:] or 2)
        graph = topo.ring_graph(n, k=min(k, (n - 1) // 2 or 1))
    elif fed.graph == "full":
        graph = topo.fully_connected_graph(n)
    elif fed.graph.startswith("geo"):
        graph = topo.geographic_graph(n, float(fed.graph[3:]), seed=0)
    elif fed.graph.startswith("er"):
        graph = topo.erdos_renyi_graph(n, float(fed.graph[2:]), seed=0)
    else:
        raise ValueError(f"unknown graph {fed.graph!r}")
    mixing = MixingDistribution(graph, p_fail=fed.p_fail,
                                scheme="metropolis")
    # 'permute' is a gossip_fn built on the mesh (make_permute_gossip), not
    # a FedDecConfig impl — the config falls back to dense there; any other
    # unknown impl is left for FedDecConfig's validation to reject
    impl = "dense" if fed.gossip_impl == "permute" else fed.gossip_impl
    fcfg = feddec.FedDecConfig(mixing=mixing, h=fed.h,
                               k=min(fed.k, n), gossip_impl=impl,
                               gossip_compress=fed.gossip_compress,
                               delta=fed.delta)
    return fcfg, n


def sweep_lattice_configs(fcfg: feddec.FedDecConfig, fed: FedConfig | None,
                          sweep_runs: int,
                          sweep_axis: str = "seed") -> list:
    """Per-run FedDecConfigs for a --sweep-runs lattice.

    ``seed``     — R replicas of the base config (the runs differ only in
                   their per-run PRNG keys, supplied by the driver);
    ``h``        — doubling server-period lattice H·{1, 2, 4, …} (the
                   paper's Fig. 4 axis);
    ``topology`` — R independent draws of the base graph family (geo/er
                   re-drawn with seed = run index; deterministic families
                   have nothing to sweep and are rejected).
    """
    fed = fed or FedConfig()
    if sweep_axis == "seed":
        return [fcfg] * sweep_runs
    if sweep_axis == "h":
        return [dataclasses.replace(fcfg, h=fcfg.h * (1 << r))
                for r in range(sweep_runs)]
    if sweep_axis == "topology":
        n = fcfg.n_agents
        if fed.graph.startswith("geo"):
            graphs = [topo.geographic_graph(n, float(fed.graph[3:]), seed=r)
                      for r in range(sweep_runs)]
        elif fed.graph.startswith("er"):
            graphs = [topo.erdos_renyi_graph(n, float(fed.graph[2:]), seed=r)
                      for r in range(sweep_runs)]
        else:
            raise ValueError(
                f"--sweep-axis topology needs a random graph family "
                f"(geoR/erP), got {fed.graph!r}")
        return [dataclasses.replace(
            fcfg, mixing=MixingDistribution(g, p_fail=fed.p_fail,
                                            scheme="metropolis"))
            for g in graphs]
    raise ValueError(f"unknown sweep_axis {sweep_axis!r}; choose "
                     f"seed|h|topology")
