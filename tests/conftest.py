import os

# Tests run on the single real CPU device; ONLY the dry-run subprocesses
# use placeholder devices (they set XLA_FLAGS themselves).
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# ...and leave no persistent compile cache behind in the checkout
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(0)
