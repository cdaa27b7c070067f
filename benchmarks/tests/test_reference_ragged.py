"""The plain reference over ragged entities against the dense one it
stands in for, and its size classes."""
import numpy as np
import pytest

from benchmarks import datagen, reference
from benchmarks.references import entities_ragged
from benchmarks.tests.conftest import load, tiny


@pytest.fixture(scope="module")
def equal_rows():
    """``game-logistic-user-re`` at the tests' size: 64 users x 16 rows."""
    config = tiny(load("benchmarks", "configs", "game-logistic-user-re.json"))
    ds = datagen.generate(config["data"], 11)
    tr = ds.train
    args = (tr.users, tr.ui, tr.uv, tr.y, ds.n_users, ds.user_dim, 1.0,
            ds.user_dim - 1)
    rng = np.random.default_rng(3)
    return (reference.PerUserLogistic.build(*args),
            entities_ragged.RaggedUserLogistic.build(*args),
            rng.normal(size=(ds.n_users, ds.user_dim)),
            rng.normal(size=tr.n_rows))


def test_gradient_residual_and_scores_equal_the_dense_reference(equal_rows):
    """At equal rows a user the two state the same sums in another order:
    1e-12 relative is float64's rounding of sums of 16 terms, with room."""
    dense, ragged, w, offsets = equal_rows
    np.testing.assert_allclose(ragged.gradient(w, offsets),
                               dense.gradient(w, offsets), rtol=1e-12, atol=1e-12)
    assert ragged.residual(w, offsets) == pytest.approx(
        dense.residual(w, offsets), rel=1e-12)
    np.testing.assert_allclose(ragged.scores(w), dense.scores(w),
                               rtol=1e-12, atol=1e-12)


def test_blocks_of_rows_change_nothing(equal_rows, monkeypatch):
    _, ragged, w, offsets = equal_rows
    whole = ragged.gradient(w, offsets), ragged.value(w, offsets)
    monkeypatch.setattr(entities_ragged, "BLOCK_ROWS", 100)   # 11 blocks
    np.testing.assert_allclose(ragged.gradient(w, offsets), whole[0],
                               rtol=1e-12, atol=1e-12)
    np.testing.assert_allclose(ragged.value(w, offsets), whole[1], rtol=1e-12)
    np.testing.assert_allclose(ragged.scores(w),
                               equal_rows[0].scores(w), rtol=1e-12, atol=1e-12)


def test_the_gradient_is_the_objectives(equal_rows):
    """Central differences of ``value`` along one direction a user: 1e-6
    relative is the truncation error of a step of 1e-5."""
    _, ragged, w, offsets = equal_rows
    d = np.random.default_rng(4).normal(size=w.shape)
    h = 1e-5
    slope = (ragged.value(w + h * d, offsets)
             - ragged.value(w - h * d, offsets)) / (2 * h)
    np.testing.assert_allclose(
        slope, (ragged.gradient(w, offsets) * d).sum(1), rtol=1e-6, atol=1e-8)


def test_one_class_that_never_moved_reads_one_and_hides_in_the_pool():
    """Ragged users: three of 8 rows, forty of 100. The small class left
    at zero reads a residual of 1 in its class and little in the pool."""
    rng = np.random.default_rng(0)
    counts = np.r_[np.full(3, 8), np.full(40, 100)]
    users = np.repeat(np.arange(43), counts)
    rng.shuffle(users)
    n, dim = len(users), 5
    ui = np.concatenate([rng.integers(0, dim - 1, size=(n, 1)),
                         np.full((n, 1), dim - 1)], axis=1)
    uv = np.concatenate([rng.normal(size=(n, 1)), np.ones((n, 1))], axis=1)
    y = (rng.random(n) < 0.5).astype(float)
    p = entities_ragged.RaggedUserLogistic.build(
        users, ui, uv, y, 43, dim, 1.0, dim - 1)
    assert sorted(set(entities_ragged.size_classes(p.counts))) == [8, 128]
    offsets = np.zeros(n)
    w = np.zeros((43, dim))
    for _ in range(200):                      # plain gradient descent
        w -= 0.01 * p.gradient(w, offsets)
    w[:3] = 0.0                               # the three small users unmoved
    by_class = p.residual_by_class(w, offsets)
    assert by_class[8] == pytest.approx(1.0)
    assert by_class[128] < 1e-3
    assert p.residual(w, offsets) < 0.2
