import os
import sys

import numpy as np
import pytest

# the benchmark's plain references (`bench/models/`) are imported by tests
# that compare the program with them; `bench` sits beside `src`
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.append(ROOT)


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)
