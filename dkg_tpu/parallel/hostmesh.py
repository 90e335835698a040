"""Force a virtual multi-device CPU backend for sharding tests/dryruns.

Multi-chip TPU hardware is not available in this environment; sharding
correctness is validated on an n-virtual-device CPU mesh.  The forcing
logic is ordering-sensitive and lives here ONCE — tests/conftest.py and
__graft_entry__.dryrun_multichip both call it.

Why each step is needed:

* ``jax.config.update("jax_platforms", "cpu")`` is the load-bearing
  platform switch.  An env var alone cannot do this job: jax binds
  ``JAX_PLATFORMS`` into its config default at import time, so a
  process that already imported jax (pytest plugins, a driver) would
  ignore a later ``os.environ`` write.  The config-level update works
  even if jax is already imported (but not yet initialised).
* ``--xla_force_host_platform_device_count=N`` is read from
  ``XLA_FLAGS`` at backend initialisation (later than jax import, so
  setting it here still works); a stale count from a previous setting
  is REWRITTEN, not kept, so the mesh really has N devices.
* ``os.environ["JAX_PLATFORMS"] = "cpu"`` only matters for
  *subprocesses* this process spawns — for the current process the
  config update above is what forces the platform.

Only effective before the first backend initialisation (jax caches the
device list); ``mesh.make_mesh`` raises if the resulting device count
falls short of what a caller asked for.  ``tests/test_import_hygiene.py``
guards the prerequisite: importing ``dkg_tpu`` must never initialise a
backend (no module-level device constants).
"""

from __future__ import annotations

import os
import re

_FLAG = "xla_force_host_platform_device_count"


def force_cpu_mesh(n_devices: int) -> None:
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    repl = f"--{_FLAG}={n_devices}"
    if _FLAG in flags:
        flags = re.sub(rf"--{_FLAG}=\d+", repl, flags)
    else:
        flags = (flags + " " + repl).strip()
    os.environ["XLA_FLAGS"] = flags

    import jax

    jax.config.update("jax_platforms", "cpu")
