"""Test environment: force an 8-device virtual CPU mesh (SURVEY.md §4).

Every parallelism test runs on this fake mesh; the chip is exercised by
``chip_smoke.py`` (see .claude/skills/verify/SKILL.md).  This mirrors the
reference's use of a CPU/Gloo ProcessGroup as the no-GPU collective fallback.

The platform is pinned through jax's config, not only the environment, so
the suite stays on the CPU even when run on a host that has a chip.
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)
# The suite compiles each program once, so the persistent compile cache the
# package turns on (paddle_tpu/__init__.py) can only cost here: on the CPU,
# writing an entry adds ~40% to the compile it stores (measured, PR 21).
# Set before jax is imported; the worker processes tests start inherit it.
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

import jax
import pytest

jax.config.update("jax_platforms", "cpu")

assert jax.device_count() == 8, f"expected 8 virtual cpu devices, got {jax.devices()}"


@pytest.fixture(autouse=True, scope="module")
def _leave_no_topology_behind():
    """A test file leaves the process-wide hybrid topology as every file
    finds it: none.  ``fleet.init`` in a fixture or a test has no teardown
    of its own, and under ``--dist loadfile`` which files share a worker, and
    in which order, changes with every run: a mesh left behind by one file
    sharded the next file's parameters or refused its Mosaic kernels
    (``test_zero_sp``, ``test_smoke_rehearsal``, the engine tests)."""
    yield
    from paddle_tpu.distributed import topology

    topology.set_hybrid_communicate_group(None)
