"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state — the 512-device
host-platform override in dryrun.py must be set before the first jax call.

Mesh shapes (TPU v5e):
  single-pod : (16, 16)    axes ('data', 'model')   = 256 chips
  multi-pod  : (2, 16, 16) axes ('pod', 'data', 'model') = 512 chips
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType

__all__ = ["make_production_mesh", "make_host_mesh", "make_agent_mesh",
           "make_fed_mesh"]


def _auto(n: int) -> tuple[AxisType, ...]:
    """Auto axis types: GSPMD propagates shardings and
    ``with_sharding_constraint`` takes plain PartitionSpecs (``jax.make_mesh``
    defaults to Explicit axes, which carry shardings in the types)."""
    return (AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, _auto(len(shape)))


def make_host_mesh(data: int = 1, model: int = 1) -> jax.sharding.Mesh:
    """Small mesh over the actually-present devices (tests / examples)."""
    return jax.make_mesh((data, model), ("data", "model"), _auto(2))


def make_agent_mesh(n_shards: int,
                    axis_name: str = "agents") -> jax.sharding.Mesh:
    """1-D mesh for the sharded flat engine (repro.core.sharded).

    The flat (n_agents, D) buffer is block-sharded over this single axis —
    each device owns n_agents/n_shards whole agent rows; the model dims stay
    unsharded (the flat layout trades inner tensor parallelism for
    whole-buffer ops).  On CPU CI the devices come from
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.
    """
    avail = len(jax.devices())
    if not 1 <= n_shards <= avail:
        raise ValueError(
            f"need 1 <= n_shards <= {avail} available devices, got "
            f"{n_shards} (force host devices with XLA_FLAGS="
            f"--xla_force_host_platform_device_count=N on CPU)")
    return jax.make_mesh((n_shards,), (axis_name,), _auto(1),
                         devices=jax.devices()[:n_shards])


def make_fed_mesh(n_agent_shards: int, n_model_shards: int = 1,
                  agent_axis: str = "agents",
                  model_axis: str = "model") -> jax.sharding.Mesh:
    """2-D ('agents', 'model') mesh for the model-sharded flat engine.

    The generalization of :func:`make_agent_mesh`: the flat (n_agents, D)
    buffer is block-sharded over ``agent_axis`` (n_agents/A whole rows per
    mesh row) AND column-sharded over ``model_axis`` (each device owns a
    D/M slice of its rows), so per-device state scales as ``1/(A·M)``.
    Gossip/server collectives run over ``agent_axis`` only; each agent
    replica's model compute is tensor-sharded over ``model_axis``
    (repro.core.sharded's 2-D lowering).

    ``make_fed_mesh(A, 1)`` covers the same device list as
    ``make_agent_mesh(A)`` and lowers the identical 1-D engine (the model
    axis of size 1 carries no collectives).  Uses the first A·M available
    devices in row-major (agents-major) order; on CPU force devices with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.
    """
    avail = len(jax.devices())
    if n_agent_shards < 1 or n_model_shards < 1 \
            or n_agent_shards * n_model_shards > avail:
        raise ValueError(
            f"need n_agent_shards >= 1, n_model_shards >= 1 and "
            f"n_agent_shards * n_model_shards <= {avail} available devices, "
            f"got ({n_agent_shards}, {n_model_shards}) (force host devices "
            f"with XLA_FLAGS=--xla_force_host_platform_device_count=N on "
            f"CPU)")
    n_dev = n_agent_shards * n_model_shards
    return jax.make_mesh((n_agent_shards, n_model_shards),
                         (agent_axis, model_axis), _auto(2),
                         devices=jax.devices()[:n_dev])
