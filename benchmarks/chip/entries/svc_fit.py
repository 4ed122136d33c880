"""Entry ``svc_fit``: ``repro.svm.SVC(C, gamma).fit(X, y)`` as a user calls
it, binary or one-vs-rest.

Traffic keys: ``C``, ``gamma`` (a float or ``"scale"``), ``impl`` and
``engine`` (``"auto"``: the fused engine on one chip, the lane-sharded
engine over every attached chip for a multiclass fit) and, optionally,
``precompute`` (the program's default when absent).  Lanes are the
class heads: one for a binary fit (``classes_[1]`` against the rest), one
per class, in sorted label order, for one-vs-rest.
"""

from __future__ import annotations

import numpy as np


def job(X, y, traffic, *, c_scale: float = 1.0):
    """One fit; returns the fitted estimator."""
    from repro.svm import SVC
    clf = SVC(C=traffic["C"] * c_scale, gamma=traffic["gamma"],
              impl=traffic["impl"], engine=traffic["engine"],
              **{k: traffic[k] for k in ("precompute",) if k in traffic})
    return clf.fit(X, y)


def warmup(X, y, traffic):
    """The same fit with C = 0: every box is empty, so each head's gap is
    0 at its first check and the loop exits at once, but every program of
    the fit is compiled (or loaded from the cache)."""
    return job(X, y, traffic, c_scale=0.0)


def returned(clf):
    """What the user waits for: alpha and b."""
    return clf.alpha_, clf.b_


def lanes(clf) -> dict:
    """Per-head arrays of a finished fit, (B, ...) in head order."""
    r = clf.fit_result_
    l = clf.alpha_.shape[-1]
    return {"alpha": np.asarray(clf.alpha_).reshape(-1, l),
            "b": np.asarray(clf.b_).reshape(-1),
            "iterations": np.asarray(r.iterations).reshape(-1),
            "converged": np.asarray(r.converged).reshape(-1)}


def problems(X, y, traffic) -> dict:
    """The heads' problems, worked out by the benchmark: labels (B, l),
    C (B,) and gamma (B,); ``gamma="scale"`` is 1 / (d Var X)."""
    classes = np.unique(y)
    heads = classes[1:] if len(classes) == 2 else classes
    labels = np.where(np.asarray(y)[None, :] == heads[:, None], 1.0, -1.0)
    g = traffic["gamma"]
    if g == "scale":
        g = 1.0 / (X.shape[1] * np.asarray(X, np.float64).var())
    B = len(heads)
    return {"labels": labels.astype(np.float32),
            "C": np.full(B, float(traffic["C"])),
            "gamma": np.full(B, float(g))}
