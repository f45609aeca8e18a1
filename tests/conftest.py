import os
import sys

# Tests run on ONE CPU device (the dry-run sets its own 512-device env in
# a subprocess); keep threads bounded for the single-core container.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_enable_x64", False)


@pytest.fixture(autouse=True)
def _no_programs_from_earlier_tests():
    """Each test starts with no built programs: those of a test run
    earlier in the same worker would otherwise be shared with it."""
    build = sys.modules.get("repro.parallelism.build")
    if build is not None:
        build.PROGRAMS.clear()
    yield
