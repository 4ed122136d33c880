"""The plain reference against float64 numpy, and its box check."""

import numpy as np

import data
import reference


def _problem(l=1500, seed=3):
    cfg = {"l": l, "d": 22, "generator": "gaussian_blobs",
           "generator_args": {"sep": 2.0}, "data_seed": 0}
    X, y = data.make(cfg, seed)
    C = np.array([0.5, 8.0, 8.0])
    gamma = np.array([2.0 ** -3, 2.0 ** -3, 2.0 ** -7])
    labels = np.broadcast_to(y, (3, l)).astype(np.float32)
    rng = np.random.default_rng(seed)
    U = np.maximum(0, labels * C[:, None])
    L = np.minimum(0, labels * C[:, None])
    alpha = np.where(rng.uniform(size=(3, l)) < 0.5,
                     rng.uniform(L, U), 0).astype(np.float32)
    b = rng.normal(size=3).astype(np.float32)
    return X, labels, C, gamma, alpha, b, L, U


def test_gradient_gap_and_bias_match_float64():
    X, labels, C, gamma, alpha, b, L, U = _problem()
    r = reference.check(X, labels, C, gamma, alpha, b)
    X64 = X.astype(np.float64)
    sq = (X64 * X64).sum(1)
    d2 = np.maximum(sq[:, None] + sq[None, :] - 2 * X64 @ X64.T, 0)
    for k in range(3):
        G = labels[k] - np.exp(-gamma[k] * d2) @ alpha[k].astype(np.float64)
        up, dn = alpha[k] < U[k], alpha[k] > L[k]
        gu, gd = G[up].max(), G[dn].min()
        # the deciding rows are float64 sums: agreement to rounding
        assert abs(r["gap"][k] - (gu - gd)) <= 1e-9 * np.abs(G).max()
        assert abs(r["b_ref"][k] - 0.5 * (gu + gd)) <= 1e-9 * np.abs(G).max()
    assert (r["box"] == 0).all()


def test_box_excess_is_exact():
    X, labels, C, gamma, alpha, b, L, U = _problem(l=300)
    alpha = alpha.copy()
    i = int(np.argmax(U[1]))
    alpha[1, i] = np.nextafter(np.float32(U[1, i]), np.float32(np.inf))
    r = reference.check(X, labels, C, gamma, alpha, b)
    assert r["box"][0] == 0 and r["box"][2] == 0
    assert 0 < r["box"][1] < 1e-5
