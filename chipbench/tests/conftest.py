"""The benchmark's own tests run on the CPU at toy sizes:

    JAX_PLATFORMS=cpu python3 -m pytest chipbench/tests -q

They are not under ``tests/``, so the repo's tier-1 command does not collect
them.  The platform is pinned through JAX's config as well, so they stay on
the CPU on a host that has a chip, and the persistent compile cache is off:
every program here compiles once.
"""

import os

os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_platforms", "cpu")


@pytest.fixture(scope="session")
def toy_root(tmp_path_factory):
    """A scratch copy of the harness with toy cells dropped in as NEW files
    (``toy.py``); no file that exists is edited."""
    from . import toy

    return toy.make_root(tmp_path_factory.mktemp("toy") / "root")


@pytest.fixture(scope="session")
def toy_spec(toy_root):
    from chipbench import spec

    return spec.Spec(root=toy_root)
