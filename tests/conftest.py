import os
import sys

# Smoke tests and benches must see ONE device; only launch/dryrun.py (run
# as its own process) sets the 512-device flag.
assert "xla_force_host_platform_device_count" not in \
    os.environ.get("XLA_FLAGS", ""), \
    "tests must not inherit the dry-run's forced device count"

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def key():
    return jax.random.PRNGKey(0)


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture(scope="session")
def lane_padded():
    """Zero-pad the columns of x to the 128-lane width the ops layer hands
    every Pallas kernel.  XLA:CPU picks its dot kernel, and so its f32
    reduction order, by shape: a scan lowering fed the padded x contracts
    the same shapes as the kernel and must match it bit for bit, where the
    unpadded scan may differ from it in the last bits."""
    def pad(x):
        x = jax.numpy.asarray(x)
        return jax.numpy.pad(x, ((0, 0), (0, 128 - x.shape[1])))
    return pad
