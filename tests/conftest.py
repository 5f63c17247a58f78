import os
import sys

# src on sys.path before the bootstrap import below — deterministic,
# regardless of whether pytest.ini's pythonpath took effect yet
_SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                    "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)

from repro.hostdevices import force_host_devices

# The suite runs on CPU with 8 forced host devices so the sharded round
# engine (repro.fl.sharded) is exercised for real — shard_map over 1/2/8
# devices — without a TPU.  Flags land before jax initializes its backend;
# an externally-provided force_host flag wins.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
force_host_devices()

import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture(scope="session")
def round_mesh():
    """Factory: 1-D ("data",) mesh over the first n forced CPU devices.

    ``round_mesh(8)`` etc. — skips (rather than errors) when the process
    has fewer devices than requested, so the suite degrades gracefully if
    run without the forced-device flag."""
    import jax

    from repro.launch.mesh import make_round_mesh

    def make(n: int):
        if len(jax.devices()) < n:
            pytest.skip(f"needs {n} devices, have {len(jax.devices())}")
        return make_round_mesh(n)

    return make
