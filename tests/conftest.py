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


import pytest  # noqa: E402 — after the backend forcing above


@pytest.fixture(scope="module")
def free_compiled_programs():
    """Drop every compiled program when the requesting file is done
    (``pytestmark = pytest.mark.usefixtures("free_compiled_programs")``).
    XLA:CPU maps a few memory regions per executable and the jit caches
    keep them all; the files whose every case compiles its own programs
    (test_fields.py alone leaves 16 k mappings) took the one-process
    tier-1 run over ``vm.max_map_count`` (65530) two thirds through,
    where it died inside a compile (PR 26).  The clear is process-wide:
    what earlier files of the process compiled goes too."""
    yield
    import jax

    jax.clear_caches()
