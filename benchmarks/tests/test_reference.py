"""The plain reference against itself: its gradient against differences of
its value, its optimizer's steps, its AUC against counting pairs, users
with unequal numbers of rows, and the refusal of what it does not state."""
import numpy as np
import pytest

from benchmarks import datagen, reference
from benchmarks.tests.conftest import load, tiny


@pytest.fixture(scope="module")
def ds():
    cfg = tiny(load("benchmarks", "configs", "game-logistic-user-re.json"))
    return datagen.generate(cfg["data"], 3)


def problem(ds, offsets=None):
    tr = ds.train
    return reference.objective("LOGISTIC_REGRESSION")(
        idx=tr.gi, val=tr.gv, y=tr.y,
        offsets=np.zeros(tr.n_rows) if offsets is None else offsets,
        dim=ds.global_dim, l2=1.0, intercept=ds.global_dim - 1)


def test_gradient_matches_differences(ds):
    p = problem(ds)
    rng = np.random.default_rng(0)
    w = rng.normal(size=p.dim) * 0.1
    g = p.gradient(w)
    for j in (0, 17, p.dim - 1):
        e = np.zeros(p.dim)
        e[j] = 1e-6
        num = (p.value_from_margins(p.margins(w + e), w + e)
               - p.value_from_margins(p.margins(w - e), w - e)) / 2e-6
        assert g[j] == pytest.approx(num, rel=1e-5, abs=1e-7)


def test_intercept_is_not_regularized(ds):
    p = problem(ds)
    assert p.lam[ds.global_dim - 1] == 0.0 and p.lam[0] == 1.0


def test_steps_descend_and_start_at_n_log2(ds):
    out = reference.optimizer("LBFGS")(problem(ds), 3)
    v = out["values"]
    assert len(v) == 4 and v[0] == pytest.approx(ds.train.n_rows * np.log(2))
    assert v[0] > v[1] > v[2] > v[3]
    assert len(out["grad_norms"]) == 4 and out["grad_norms"][3] < out["grad_norms"][0]


def test_a_line_search_that_rounding_could_decide_is_counted_out():
    """At ``glm_fit``'s own size: seed 2147483661's first search takes a step
    that passes the Armijo test by 1.2 of an objective of 45,426, which a
    float32 sum cannot vouch for, where half the step gains 216 (on the chip
    the program halved once more, PERF.md §2); seed 7000's searches are all
    beyond rounding."""
    cfg = load("benchmarks", "configs", "glm-logistic-l2.json")
    sure = {}
    for seed in (2147483661, 7000):
        big = datagen.generate(cfg["data"], seed)
        sure[seed] = reference.lbfgs(problem(big), 3)["sure"]
    assert sure == {2147483661: 0, 7000: 3}


def test_a_solve_goes_on_from_where_it_is_started(ds):
    """Ten iterations from the coefficients that three iterations left
    start at the value those ended on, and go on down."""
    p = problem(ds)
    three = reference.lbfgs(p, 3)
    more = reference.lbfgs(p, 10, three["w"])
    assert more["values"][0] == pytest.approx(three["values"][-1], rel=1e-12)
    assert more["values"][-1] < more["values"][0]
    assert np.linalg.norm(more["w"] - three["w"]) > 0


@pytest.mark.parametrize("lookup,name", [
    (reference.objective, "POISSON_REGRESSION"),
    (reference.optimizer, "TRON"),
])
def test_what_the_reference_does_not_state_is_an_error(lookup, name):
    with pytest.raises(ValueError, match=name):
        lookup(name)
    with pytest.raises(ValueError, match="RMSE"):
        reference.evaluator_gap("RMSE", 1.0, np.zeros(2), np.zeros(2))


def test_evaluator_gaps_are_relative_for_a_loss_and_absolute_for_auc():
    s = np.array([-1.0, 0.5, 2.0, -0.3])
    y = np.array([0.0, 1.0, 1.0, 0.0])
    loss = reference.mean_logistic_loss(s, y)
    assert reference.evaluator_gap("LOGISTIC_LOSS", 1.1 * loss, s, y) == (
        pytest.approx(0.1))
    assert reference.evaluator_gap("AUC", 0.9, s, y) == pytest.approx(0.1)


def test_per_user_gradient_and_scores(ds):
    tr = ds.train
    users = reference.PerUserLogistic.build(
        tr.users, tr.ui, tr.uv, tr.y, ds.n_users, ds.user_dim, 1.0,
        ds.user_dim - 1)
    rng = np.random.default_rng(1)
    w = rng.normal(size=(ds.n_users, ds.user_dim))
    off = rng.normal(size=tr.n_rows)
    # one user by hand
    u = 5
    rows = np.flatnonzero(tr.users == u)
    x = np.zeros((len(rows), ds.user_dim))
    for r, row in enumerate(rows):
        x[r, tr.ui[row]] = tr.uv[row]
    z = x @ w[u] + off[rows]
    lam = np.r_[np.ones(ds.user_dim - 1), 0.0]
    g = x.T @ (reference.sigmoid(z) - tr.y[rows]) + lam * w[u]
    assert users.gradient(w, off)[u] == pytest.approx(g)
    assert users.scores(w)[rows] == pytest.approx(x @ w[u])
    assert reference.user_scores(tr.users, tr.ui, tr.uv, w)[rows] == (
        pytest.approx(x @ w[u]))
    assert users.residual(np.zeros_like(w), off) == 1.0


def test_users_with_unequal_rows(ds):
    """A user short of rows has slots that count for nothing: dropping
    rows of one user changes that user's gradient and no one else's."""
    tr = ds.train
    keep = ~((tr.users == 5) & (np.arange(tr.n_rows) % 2 == 0))
    full = reference.PerUserLogistic.build(
        tr.users, tr.ui, tr.uv, tr.y, ds.n_users, ds.user_dim, 1.0,
        ds.user_dim - 1)
    cut = reference.PerUserLogistic.build(
        tr.users[keep], tr.ui[keep], tr.uv[keep], tr.y[keep], ds.n_users,
        ds.user_dim, 1.0, ds.user_dim - 1)
    assert cut.live.sum() == keep.sum() and not cut.live.all()
    rng = np.random.default_rng(4)
    w = rng.normal(size=(ds.n_users, ds.user_dim))
    off = rng.normal(size=tr.n_rows)
    g_full, g_cut = full.gradient(w, off), cut.gradient(w, off[keep])
    others = np.arange(ds.n_users) != 5
    assert g_cut[others] == pytest.approx(g_full[others])
    assert not np.allclose(g_cut[5], g_full[5])
    assert cut.scores(w) == pytest.approx(full.scores(w)[keep])


def test_unseen_users_score_zero(ds):
    va = ds.validation
    w = np.ones((ds.n_users, ds.user_dim))
    s = reference.user_scores(va.users, va.ui, va.uv, w)
    assert np.all(s[va.users < 0] == 0.0) and np.any(s[va.users >= 0] != 0.0)


def test_auc_counts_pairs():
    rng = np.random.default_rng(2)
    s = np.round(rng.normal(size=200), 1)          # ties on purpose
    y = (rng.random(200) < 0.4).astype(float)
    pos, neg = s[y > 0.5], s[y <= 0.5]
    wins = (pos[:, None] > neg[None, :]).sum() + 0.5 * (
        pos[:, None] == neg[None, :]).sum()
    assert reference.auc(s, y) == pytest.approx(wins / (len(pos) * len(neg)))
