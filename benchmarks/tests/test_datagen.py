"""The generator: the same seed gives the same data, another seed other
data, and a seed past 32 signed bits works."""
import numpy as np
import pytest

from benchmarks import datagen
from benchmarks.tests.conftest import load, tiny


@pytest.fixture(scope="module", params=["glm-logistic-l2", "game-logistic-user-re"])
def data(request):
    return tiny(load("benchmarks", "configs", request.param + ".json"))["data"]


def _arrays(ds):
    out = []
    for split in (ds.train, ds.validation):
        out += [a for a in (split.gi, split.gv, split.y, split.users,
                            split.ui, split.uv) if a is not None]
    return out


def test_same_seed_same_data(data):
    a, b = datagen.generate(data, 7), datagen.generate(data, 7)
    assert all(np.array_equal(x, y) for x, y in zip(_arrays(a), _arrays(b)))


def test_another_seed_other_data(data):
    a, b = datagen.generate(data, 7), datagen.generate(data, 8)
    assert not np.array_equal(a.train.gv, b.train.gv)
    assert not np.array_equal(a.train.y, b.train.y)
    assert a.train.gv.shape == b.train.gv.shape       # the same sizes


def test_large_seed(data):
    ds = datagen.generate(data, 2**31 + 12345)
    assert ds.train.n_rows > 0


def test_shapes(data):
    ds = datagen.generate(data, 1)
    assert ds.global_dim == data["named_features"] + 1
    assert ds.train.gi.shape[1] == data["named_nnz"] + 1
    assert np.all(ds.train.gi[:, -1] == ds.global_dim - 1)     # intercept
    assert np.all(ds.train.gv[:, -1] == 1.0)
    assert ds.train.gi.max() < ds.global_dim
    if ds.n_users:
        counts = np.bincount(ds.train.users, minlength=ds.n_users)
        assert np.all(counts == data["rows_per_user"])
        v = data["validation"]
        assert (ds.validation.users < 0).sum() == v["unseen_users"] * v["unseen_rows"]
        assert len(set(datagen.user_keys(ds.validation.users))) == (
            ds.n_users + v["unseen_users"])
        # a row's user-shard columns are distinct
        assert all(len(set(r)) == len(r) for r in ds.train.ui[:50].tolist())
