"""Launch layer: device meshes, input specs, the federated set-up and the
training / serving drivers."""

from repro.launch import mesh, specs, steps

__all__ = ["mesh", "specs", "steps"]
