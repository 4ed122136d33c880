"""SVC facade: fit/predict round-trips, batched-vs-single parity, shapes."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import reference
from repro.svm import SVC
from repro.svm.data import gaussian_blobs, multiclass_blobs, ring


def _binary_data(n=120, seed=0):
    X, y = ring(n, seed=seed)
    return X, y.astype(np.int64)  # labels in {-1, 1}


def test_binary_fit_predict_roundtrip():
    X, y = _binary_data()
    clf = SVC(C=10.0, gamma=1.0, eps=1e-4).fit(X, y)
    assert clf.score(X, y) > 0.95
    df = clf.decision_function(X)
    assert df.shape == (len(y),)
    # sign(decision) maps to classes_[df >= 0]
    pred = clf.predict(X)
    np.testing.assert_array_equal(pred, clf.classes_[(np.asarray(df) >= 0)
                                                     .astype(int)])


def test_multiclass_fit_predict_roundtrip():
    X, y = multiclass_blobs(150, seed=1, k=3)
    clf = SVC(C=10.0, gamma=0.5, eps=1e-4).fit(X, y)
    assert clf.score(X, y) > 0.8
    df = clf.decision_function(X)
    assert df.shape == (len(y), 3)
    assert clf.alpha_.shape == (3, len(y))
    assert set(clf.predict(X)) <= set(clf.classes_)
    # held-out data from the same distribution
    Xq, yq = multiclass_blobs(60, seed=9, k=3)
    assert clf.score(Xq, yq) > 0.7


def test_batched_vs_single_example_predict_parity():
    X, y = multiclass_blobs(90, seed=2, k=3)
    clf = SVC(C=5.0, gamma=0.7, eps=1e-4).fit(X, y)
    Xq, _ = multiclass_blobs(25, seed=3, k=3)
    batched = clf.predict(Xq)
    singles = np.array([clf.predict(Xq[i]) for i in range(len(Xq))])
    np.testing.assert_array_equal(batched, singles)
    df_b = np.asarray(clf.decision_function(Xq))
    for i in range(len(Xq)):
        np.testing.assert_allclose(np.asarray(clf.decision_function(Xq[i])),
                                   df_b[i], rtol=1e-10)


def test_label_dtype_preserved():
    X, y = _binary_data(80, seed=4)
    labels = np.where(y > 0, 7, 3)  # arbitrary non-contiguous labels
    clf = SVC(C=10.0, gamma=1.0, eps=1e-3).fit(X, labels)
    assert set(np.unique(clf.predict(X))) <= {3, 7}
    assert clf.score(X, labels) > 0.9


def test_gamma_scale_and_introspection():
    X, y = multiclass_blobs(80, seed=5, k=3)
    clf = SVC(C=10.0, gamma="scale", eps=1e-3).fit(X, y)
    assert clf.gamma_ > 0
    assert clf.n_support_.shape == (3,)
    assert np.all(clf.n_support_ > 0)


@pytest.mark.slow
def test_precompute_false_matches_precompute_true():
    X, y = _binary_data(70, seed=6)
    a = SVC(C=10.0, gamma=1.0, eps=1e-4, precompute=True).fit(X, y)
    b = SVC(C=10.0, gamma=1.0, eps=1e-4, precompute=False).fit(X, y)
    np.testing.assert_allclose(float(a.fit_result_.objective),
                               float(b.fit_result_.objective), rtol=1e-8)
    np.testing.assert_array_equal(a.predict(X), b.predict(X))


def test_unfitted_and_degenerate_errors():
    with pytest.raises(RuntimeError):
        SVC().predict(np.zeros((3, 2)))
    with pytest.raises(ValueError):
        SVC().fit(np.zeros((4, 2)), np.zeros(4))  # single class


@pytest.mark.parametrize("dtype", [jnp.float64, jnp.float32])
def test_ijcnn1_shaped_fit_matches_float64_oracle(dtype):
    """LIBSVM's default binary fit at ijcnn1's shape, cut to l = 512:
    ``SVC(C=1, gamma="scale")`` on two overlapping Gaussians in 22
    dimensions, against the float64 PA-SMO oracle run to a gap of 1e-9.

    Tolerances: a gap of eps leaves the dual objective below the optimum
    by at most about eps * sum|alpha| / 2 (2.8e-3 of it here, the worst
    case); both precisions read 5e-8, so rtol 1e-5 keeps 200x of room.
    At a gap of eps each decision value is fixed to about eps / 2 around
    the optimum's (both read 4.8e-4), so atol = eps.  The true gap, from
    float64 rows of the returned alpha, is held to eps itself: in float32
    the engine's exit check reads it from exact rows (it reads 9.6e-4).
    """
    X, y = gaussian_blobs(512, seed=0, d=22, sep=2.0)
    clf = SVC(C=1.0, gamma="scale", dtype=dtype).fit(X, y)
    assert clf.alpha_.dtype == dtype and bool(clf.fit_result_.converged)
    Xd = np.asarray(X, np.float64)
    sq = np.einsum("ij,ij->i", Xd, Xd)
    K = np.exp(-clf.gamma_ * np.maximum(sq[:, None] + sq[None, :]
                                        - 2.0 * Xd @ Xd.T, 0.0))
    yb = np.where(y == clf.classes_[1], 1.0, -1.0)
    ref = reference.solve_pasmo(K, yb, 1.0, eps=1e-9)
    assert ref.converged

    a = np.asarray(clf.alpha_, np.float64)
    np.testing.assert_allclose(yb @ a - 0.5 * a @ K @ a, ref.objective,
                               rtol=1e-5)
    G, G_ref = yb - K @ a, yb - K @ ref.alpha
    U, L = np.maximum(0.0, yb), np.minimum(0.0, yb)

    def ends(G, a):
        return (np.where(a < U, G, -np.inf).max(),
                np.where(a > L, G, np.inf).min())

    g_up, g_dn = ends(G, a)
    assert g_up - g_dn <= 1e-3
    b_ref = 0.5 * sum(ends(G_ref, ref.alpha))
    np.testing.assert_allclose(K @ a + float(clf.b_), K @ ref.alpha + b_ref,
                               rtol=0, atol=1e-3)
