"""Launch layer: production mesh, input specs, dry-run, drivers.

NOTE: do NOT import repro.launch.dryrun from library/test code — it sets
the 512-device host-platform override at import time and must run as its
own process.
"""

from repro.launch import mesh, specs, steps

__all__ = ["mesh", "specs", "steps"]
