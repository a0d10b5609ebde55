"""Test env: force an 8-device virtual CPU mesh before jax is imported.

Stands in for a TPU pod the way the reference's `local[4]` Spark master
stands in for a cluster (reference `core/src/test/.../BaseTest.scala:14-74`).
"""

import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent.parent))

# Tests run on the CPU: JAX_PLATFORMS=cpu is the one way to ask for it.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
# a fixed place outside the checkout for everything the suite (and the
# CLI subprocesses it spawns) compiles; the same path on every run, so
# the second run of a test is a cache hit
os.environ["JAX_COMPILATION_CACHE_DIR"] = "/tmp/pio_tpu_test_jax_cache"

import pytest  # noqa: E402


@pytest.fixture()
def storage_memory():
    """Process-global Storage wired to hermetic in-memory backends."""
    from predictionio_tpu.storage import Storage, reset_storage

    s = Storage(env={
        "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "MEM",
        "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MEMDB",
        "PIO_STORAGE_SOURCES_MEM_TYPE": "memory",
        "PIO_STORAGE_SOURCES_MEMDB_TYPE": "memory",
    })
    reset_storage(s)
    yield s
    reset_storage(None)
