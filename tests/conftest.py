# NOTE: no XLA_FLAGS here on purpose — smoke tests and benches must see the
# real single CPU device.  Multi-device tests spawn subprocesses that set
# --xla_force_host_platform_device_count themselves (see test_dist_sort.py).
import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(0)
