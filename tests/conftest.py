"""Test configuration: force an 8-virtual-device CPU mesh.

Multi-chip TPU hardware is not available in CI; sharding correctness is
validated on a virtual CPU mesh exactly as the driver's dryrun does.
Must run before any jax *backend initialisation* (hostmesh.py explains
the ordering; test_import_hygiene.py guards it).
"""

import os

if os.environ.get("DKG_TPU_TEST_BACKEND") == "tpu":
    # TPU test tier: run on the real chip (Mosaic kernel parity tests
    # un-skip themselves via jax.default_backend() == "tpu").
    pass
else:
    from dkg_tpu.parallel.hostmesh import force_cpu_mesh

    force_cpu_mesh(8)

# Persistent compile cache policy.
#
# CPU tier: OFF by default.  Serializing/deserializing this package's
# very large XLA:CPU executables has segfaulted repeatedly inside the
# cache writer AND reader (jax compilation_cache put/get_executable) on
# this image — a poisoned entry then crashes every later run.  Paying
# the recompiles is slower but reliable; DKG_TPU_TEST_CACHE=1 opts back
# in for local iteration (delete the dir if a run ever segfaults in
# compilation_cache.py).
#
# TPU tier: ON — those executables serialize fine and kernel compiles
# are expensive.  Where the cache goes: dkg_tpu/utils/compilecache.py.
if (
    os.environ.get("DKG_TPU_TEST_BACKEND") == "tpu"
    or os.environ.get("DKG_TPU_TEST_CACHE") == "1"
):
    from dkg_tpu.utils import compilecache

    compilecache.enable()
