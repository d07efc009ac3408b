import os

# smoke tests and benches run on the CPU; the multi-device tests set their own devices
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax
import numpy as np
import pytest

jax.config.update("jax_enable_x64", False)


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture()
def key():
    import jax.random

    return jax.random.PRNGKey(0)
