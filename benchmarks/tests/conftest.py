"""Run with ``pytest benchmarks/tests -q`` from the root of the checkout
(``JAX_PLATFORMS=cpu``; nothing here needs or touches a chip)."""
import copy
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def tiny(config: dict) -> dict:
    """A configuration file at a size a test run can hold: the same
    coordinates and mathematics, 256 features and a thousand rows."""
    c = copy.deepcopy(config)
    d = c["data"]
    d.update(named_features=255, named_nnz=7, head_features=32)
    if "users" in d:
        d.update(users=64, rows_per_user=16, user_features=4, user_nnz=2,
                 validation={"rows_per_user": 4, "unseen_users": 4,
                             "unseen_rows": 2})
    else:
        d.update(rows=2048, validation={"rows": 512})
    return c


@pytest.fixture(scope="session")
def bench():
    return load("BENCHMARK.json")


@pytest.fixture(scope="session")
def mix():
    return load("benchmarks", "traffic", "fit_from_zero.json")
