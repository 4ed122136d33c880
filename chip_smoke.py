#!/usr/bin/env python3
"""Run the PA-SMO main path on a TPU chip once and check what comes out.

    python chip_smoke.py                # one chip: phases 1 and 2
    python chip_smoke.py --four-chips   # the lane-sharded grid on 4 chips

Everything runs in one process, in float32 with ``jax_enable_x64`` off,
through the entry points a user calls (the ``SVC``/``SVR`` facades and
``grid.solve_grid``), on data generated from ``--seed`` by
:mod:`repro.svm.data` at published dataset shapes.

* Phase 1, parity at reduced width (l = 2,048, d = 22): a binary ``SVC``
  and an ε-``SVR`` fit (the doubled kernels) with ``impl="auto"``, compared
  on the host with the float64 numpy oracle
  :func:`repro.core.reference.solve_qp_smo` on the dual objective and on
  held-out decision values.
* Phase 2, full width: (a) a 10-class ``SVC(C=10, gamma="scale")`` at the
  mnist shape (l = 60,000, d = 780; 10 one-vs-rest lanes); (b)
  ``solve_grid`` over a 3 x 3 (C, gamma) slice of the LIBSVM-guide grid at
  the ijcnn1 shape (l = 49,990, d = 22).  Each runs on the Pallas kernels
  and again on the XLA path (``impl="jnp", precompute=False``); objectives
  must agree and every lane must converge to ``eps = 1e-3``.
* ``--four-chips`` runs phase 2b's grid only, lane-sharded over every
  attached chip, against the single-device fused engine on the same lanes.

The script exits non-zero, and prints no result line, unless JAX's first
device is a TPU, ``impl="auto"`` resolves to the Pallas kernels and every
phase passes.  Its last stdout line is the result JSON.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
EPS = 1e-3                                   # LIBSVM's default KKT gap
C_GRID = 2.0 ** np.array([-1.0, 1.0, 3.0])      # log2 C in the guide's grid
GAMMA_GRID = 2.0 ** np.array([-3.0, -5.0, -7.0])

# --- tolerances, each with its float32 reason ------------------------------
# Objective against the f64 oracle.  Both solvers stop at a point whose
# maximal KKT violation is below EPS, not at the optimum, and float32
# rounding steers the chip's trajectory to a different such point; the
# dual objective is flat near the optimum (second order in the distance),
# so the two agree far more tightly than EPS.  float32 itself (unit
# roundoff 6e-8) accumulated over the ~1e3-5e3 rank-2 updates of a
# 2048-row fit bounds the arithmetic part near 1e-6 relative.
OBJ_REL_ORACLE = 1e-5
# Held-out decision values against the f64 oracle: an EPS-optimal dual
# moves a decision value by up to about EPS (the bias is the midpoint of
# KKT gap endpoints that may differ by EPS), plus the f32 kernel rows
# (relative error ~1e-6 per entry over 2048 terms).
DEC_ABS_ORACLE = 2 * EPS
# Pallas against the XLA path at full width: the same float32 algorithm
# with different reduction orders, so trajectories may split and stop at
# different EPS-optimal points; as for the oracle, the objective moves at
# second order, and float32 accumulation over ~1e5 updates stays below
# 1e-5 relative.
OBJ_REL_PATHS = 1e-5
# Four chips against one: the same per-lane program and kernels, lanes are
# independent, so results should be bitwise equal; the bound only allows
# for a lane-batch-shaped XLA fusion of the O(B) step algebra.
OBJ_REL_SHARDED = 1e-6


def log(msg: str) -> None:
    print(msg, flush=True)


class Failed(Exception):
    """A phase produced a wrong or unconverged result."""


def check(ok: bool, what: str) -> None:
    log(f"  check {'ok  ' if ok else 'FAIL'} {what}")
    if not ok:
        raise Failed(what)


def rel(a, b):
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return np.abs(a - b) / np.maximum(1.0, np.abs(b))


def compiled_text(fn, *args) -> tuple[str, float]:
    """Compile ``fn`` ahead of the run; return its HLO text and the
    seconds the compile took (set-up, reported apart from the run)."""
    import jax
    t0 = time.perf_counter()
    text = jax.jit(fn).lower(*args).compile().as_text()
    return text, time.perf_counter() - t0


def check_kernels_in(text: str, what: str) -> None:
    n = text.count("tpu_custom_call")
    log(f"  compiled {what}: {n} tpu_custom_call op(s)")
    check(n > 0, f"{what} runs the Pallas kernels (tpu_custom_call)")


# ---------------------------------------------------------------------------
# host float64 oracle
# ---------------------------------------------------------------------------


def rbf64(A, B, gamma):
    d2 = ((A * A).sum(1)[:, None] + (B * B).sum(1)[None, :]
          - 2.0 * A @ B.T)
    return np.exp(-gamma * np.maximum(d2, 0.0))


def oracle(Q, p, L, U):
    """f64 SMO to EPS; returns (alpha, objective, bias, iterations)."""
    from repro.core.reference import solve_qp_smo
    r = solve_qp_smo(Q, p, L, U, eps=EPS)
    G = p - Q @ r.alpha
    g_up = np.max(np.where(r.alpha < U, G, -np.inf))
    g_dn = np.min(np.where(r.alpha > L, G, np.inf))
    return r.alpha, r.objective, 0.5 * (g_up + g_dn), r.iterations


# ---------------------------------------------------------------------------
# phase 1: parity against the f64 oracle at reduced width
# ---------------------------------------------------------------------------


def phase1(seed: int, impl: str, l: int = 2048, n_test: int = 512) -> None:
    import jax
    import jax.numpy as jnp
    from repro.core import qp as qp_mod
    from repro.core.reference import doubled_qp
    from repro.core.solver import SolverConfig
    from repro.core.solver_fused import solve_fused_batched_qp
    from repro.svm import SVC, SVR, data

    X, y = data.gaussian_blobs(l + n_test, seed=seed, d=22)
    Xtr, Xte = X[:l], X[l:]

    log(f"phase 1a: binary SVC, l={l} d=22, impl={impl}")
    clf = SVC(C=1.0, gamma="scale", impl=impl)
    ytr = y[:l]
    Xj = jnp.asarray(Xtr, jnp.float32)
    Yj = jnp.asarray(ytr[None], jnp.float32)
    text, t_c = compiled_text(
        lambda X_, P_, L_, U_: solve_fused_batched_qp(
            X_, P_, L_, U_, 0.05, SolverConfig(eps=EPS), impl=impl),
        Xj, Yj, jnp.minimum(0.0, Yj), jnp.maximum(0.0, Yj))
    log(f"  set-up: fused-engine compile {t_c:.3f} s")
    if impl == "auto":
        check_kernels_in(text, "SVC fit")
    for rep in ("first fit (compile included)", "second fit"):
        t0 = time.perf_counter()
        clf.fit(Xtr, ytr)
        jax.block_until_ready(clf.alpha_)
        log(f"  {rep}: {time.perf_counter() - t0:.3f} s")
    res, gamma = clf.fit_result_, clf.gamma_
    K = rbf64(Xtr, Xtr, gamma)
    a_o, obj_o, b_o, it_o = oracle(K, ytr, np.minimum(0.0, ytr),
                                   np.maximum(0.0, ytr))
    dec = np.asarray(clf.decision_function(Xte), np.float64)
    dec_o = rbf64(Xte, Xtr, gamma) @ a_o + b_o
    log(f"  chip: iterations {int(res.iterations)}, kkt_gap "
        f"{float(res.kkt_gap):.3e}, objective {float(res.objective):.6f}")
    log(f"  f64 oracle: iterations {it_o}, objective {obj_o:.6f}")
    e_obj = float(rel(res.objective, obj_o))
    e_dec = float(np.max(np.abs(dec - dec_o)))
    log(f"  objective rel err {e_obj:.3e} (tol {OBJ_REL_ORACLE:g}); "
        f"decision max abs err {e_dec:.3e} (tol {DEC_ABS_ORACLE:g}); "
        f"test accuracy {clf.score(Xte, y[l:]):.4f}")
    check(bool(res.converged), "SVC converged")
    check(e_obj <= OBJ_REL_ORACLE, "SVC objective matches the f64 oracle")
    check(e_dec <= DEC_ABS_ORACLE, "SVC decisions match the f64 oracle")

    log(f"phase 1b: epsilon-SVR (doubled kernels), l={l} d=22, impl={impl}")
    rng = np.random.default_rng(seed)
    t = np.sin(X[:, 0]) + 0.5 * X[:, 1] + 0.1 * rng.normal(size=len(X))
    reg = SVR(C=1.0, epsilon=0.1, gamma="scale", impl=impl)
    qp = qp_mod.svr_qp(jnp.asarray(t[:l], jnp.float32), 1.0, 0.1)
    text, t_c = compiled_text(
        lambda X_, P_, L_, U_: solve_fused_batched_qp(
            X_, P_, L_, U_, 0.05, SolverConfig(eps=EPS), impl=impl,
            doubled=True),
        Xj, qp.p[None], qp.bounds.lower[None], qp.bounds.upper[None])
    log(f"  set-up: fused-engine compile {t_c:.3f} s")
    if impl == "auto":
        check_kernels_in(text, "SVR fit")
    t0 = time.perf_counter()
    reg.fit(Xtr, t[:l])
    jax.block_until_ready(reg.alpha_)
    log(f"  fit: {time.perf_counter() - t0:.3f} s (compile included)")
    res, gamma = reg.fit_result_, reg.gamma_
    Q, p, L, U = doubled_qp(rbf64(Xtr, Xtr, gamma), t[:l], 1.0, 0.1)
    a_o, obj_o, b_o, it_o = oracle(Q, p, L, U)
    pred = np.asarray(reg.predict(Xte), np.float64)
    pred_o = rbf64(Xte, Xtr, gamma) @ (a_o[:l] + a_o[l:]) + b_o
    log(f"  chip: iterations {int(res.iterations)}, kkt_gap "
        f"{float(res.kkt_gap):.3e}, objective {float(res.objective):.6f}")
    log(f"  f64 oracle: iterations {it_o}, objective {obj_o:.6f}")
    e_obj = float(rel(res.objective, obj_o))
    e_dec = float(np.max(np.abs(pred - pred_o)))
    log(f"  objective rel err {e_obj:.3e} (tol {OBJ_REL_ORACLE:g}); "
        f"prediction max abs err {e_dec:.3e} (tol {DEC_ABS_ORACLE:g}); "
        f"test R^2 {reg.score(Xte, t[l:]):.4f}")
    check(bool(res.converged), "SVR converged")
    check(e_obj <= OBJ_REL_ORACLE, "SVR objective matches the f64 oracle")
    check(e_dec <= DEC_ABS_ORACLE, "SVR predictions match the f64 oracle")


# ---------------------------------------------------------------------------
# phase 2: full width, Pallas against the XLA path
# ---------------------------------------------------------------------------


def lane_report(name: str, res) -> None:
    it = np.asarray(res.iterations).reshape(-1)
    gap = np.asarray(res.kkt_gap).reshape(-1)
    log(f"  {name}: iterations per lane {it.tolist()}; max kkt_gap "
        f"{gap.max():.3e}; all converged "
        f"{bool(np.asarray(res.converged).all())}")


def drift_report(X, P, L, U, gammas, alpha, G) -> None:
    """Gap recomputed from an exact G = p - Q alpha against the carried G
    (float32 drift of the rank-2 updates)."""
    import jax.numpy as jnp
    from repro.kernels import row_source
    B = alpha.shape[0]
    src = row_source.rbf_source(jnp.asarray(X), jnp.asarray(gammas), B)
    G_ex = np.asarray(P, np.float64) - np.asarray(
        src.matvec(jnp.asarray(alpha)), np.float64)
    a = np.asarray(alpha, np.float64)
    up, dn = a < np.asarray(U), a > np.asarray(L)
    gap = (np.where(up, G_ex, -np.inf).max(1)
           - np.where(dn, G_ex, np.inf).min(1))
    drift = np.abs(G_ex - np.asarray(G, np.float64)).max(1)
    log(f"  recomputed G = p - Q alpha: gap per lane "
        f"{np.round(gap, 6).tolist()}; max |G drift| per lane "
        f"{np.round(drift, 7).tolist()}")


def compare_paths(name: str, r_pl, r_x) -> None:
    lane_report(f"{name} pallas", r_pl)
    lane_report(f"{name} jnp", r_x)
    e = rel(np.asarray(r_pl.objective).reshape(-1),
            np.asarray(r_x.objective).reshape(-1))
    log(f"  {name} objective rel diff pallas vs jnp per lane "
        f"{np.round(e, 8).tolist()} (tol {OBJ_REL_PATHS:g})")
    for tag, r in (("pallas", r_pl), ("jnp", r_x)):
        check(bool(np.asarray(r.converged).all()),
              f"{name} {tag}: every lane converged to eps={EPS:g}")
    check(float(e.max()) <= OBJ_REL_PATHS,
          f"{name}: Pallas and XLA objectives agree")


def phase2a(seed: int, impl: str, l: int = 60_000, d: int = 780,
            k: int = 10) -> None:
    import jax
    from repro.core import multiclass as mc
    from repro.core.solver import SolverConfig
    from repro.svm import SVC, data

    log(f"phase 2a: {k}-class SVC(C=10, gamma='scale'), l={l} d={d}")
    X, y = data.multiclass_blobs(l, seed=seed, k=k, d=d, sep=30.0)
    fits = {}
    for tag, kw in (("pallas", dict(impl=impl)),
                    ("jnp", dict(impl="jnp", precompute=False))):
        clf = SVC(C=10.0, gamma="scale", **kw)
        if tag == "pallas" and impl == "auto":
            Xj = jax.numpy.asarray(X, np.float32)
            Y = mc.ovr_labels(mc.class_index(y)[1], k, np.float32)
            text, t_c = compiled_text(
                lambda X_, Y_: mc.solve_ovr_fused(
                    X_, Y_, 10.0, 1e-3, SolverConfig(eps=EPS),
                    impl=impl), Xj, Y)
            log(f"  set-up: OVR fused-engine compile {t_c:.3f} s")
            check_kernels_in(text, "multiclass SVC fit")
        t0 = time.perf_counter()
        clf.fit(X, y)
        jax.block_until_ready(clf.alpha_)
        log(f"  {tag} fit: {time.perf_counter() - t0:.3f} s "
            f"(its jit compile included)")
        fits[tag] = clf
    r_pl, r_x = fits["pallas"].fit_result_, fits["jnp"].fit_result_
    if not bool(np.asarray(r_pl.converged).all()):
        Y = np.where(y[None] == np.arange(k)[:, None], 1.0, -1.0)
        drift_report(np.asarray(X, np.float32), Y, np.minimum(0, 10 * Y),
                     np.maximum(0, 10 * Y),
                     np.full(k, fits["pallas"].gamma_), r_pl.alpha, r_pl.G)
    Xt, yt = data.multiclass_blobs(2000, seed=seed + 1, k=k, d=d, sep=30.0)
    log(f"  held-out accuracy pallas {fits['pallas'].score(Xt, yt):.4f}, "
        f"jnp {fits['jnp'].score(Xt, yt):.4f}")
    compare_paths("2a", r_pl, r_x)


def grid_problem(seed: int, l: int = 49_990, d: int = 22):
    from repro.svm import data
    X, y = data.gaussian_blobs(l, seed=seed, d=d)
    return np.asarray(X, np.float32), np.asarray(y, np.float32)


def phase2b(seed: int, impl: str, l: int = 49_990) -> None:
    import jax
    from repro.core import grid

    log(f"phase 2b: solve_grid 3x3 (C, gamma) slice, l={l} d=22, binary; "
        f"log2 C {np.log2(C_GRID).tolist()}, log2 gamma "
        f"{np.log2(GAMMA_GRID).tolist()}")
    X, y = grid_problem(seed, l)
    if impl == "auto":
        text, t_c = compiled_text(
            lambda X_, y_: grid.solve_grid(X_, y_, C_GRID, GAMMA_GRID,
                                           impl=impl), X, y)
        log(f"  set-up: grid compile {t_c:.3f} s")
        check_kernels_in(text, "solve_grid")
    res = {}
    for tag, kw in (("pallas", dict(impl=impl)),
                    ("jnp", dict(impl="jnp", precompute=False))):
        t0 = time.perf_counter()
        r = grid.solve_grid(X, y, C_GRID, GAMMA_GRID, **kw)
        jax.block_until_ready(r.alpha)
        log(f"  {tag} solve_grid: {time.perf_counter() - t0:.3f} s "
            f"(its jit compile included)")
        res[tag] = r
    r = res["pallas"]
    lanes = lambda a: np.asarray(a).reshape(-1, a.shape[-1])
    gam = np.repeat(GAMMA_GRID, len(C_GRID)).astype(np.float32)
    Cl = np.tile(C_GRID, len(GAMMA_GRID)).astype(np.float32)[:, None]
    P = np.broadcast_to(y, (len(gam), l))
    drift_report(X, P, np.minimum(0, P * Cl), np.maximum(0, P * Cl), gam,
                 lanes(r.alpha), lanes(r.G))
    compare_paths("2b", res["pallas"], res["jnp"])


# ---------------------------------------------------------------------------
# four chips: the lane-sharded grid against the single-device engine
# ---------------------------------------------------------------------------


def four_chips(seed: int, impl: str, l: int = 49_990) -> None:
    import jax
    from repro.core import grid

    devs = jax.devices()
    log(f"four chips: solve_grid 3x3 slice, l={l} d=22, lanes sharded over "
        f"{len(devs)} devices against the single-device fused engine")
    X, y = grid_problem(seed, l)
    out = {}
    for tag, kw in (("one device", {}), ("sharded", dict(devices=devs))):
        t0 = time.perf_counter()
        r = grid.solve_grid(X, y, C_GRID, GAMMA_GRID, impl=impl, **kw)
        jax.block_until_ready(r.alpha)
        log(f"  {tag}: {time.perf_counter() - t0:.3f} s (compile included)")
        lane_report(tag, r)
        out[tag] = r
    r1, r4 = out["one device"], out["sharded"]
    shards = sorted({str(s.device) for s in r4.alpha.addressable_shards})
    log(f"  sharded result: alpha sharding {r4.alpha.sharding}; held on "
        f"{len(shards)} device(s): {shards}")
    peaks = {str(d): (d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devs}
    log(f"  peak device memory (bytes): {peaks}")
    it1 = np.asarray(r1.iterations).reshape(-1)
    it4 = np.asarray(r4.iterations).reshape(-1)
    ob1 = np.asarray(r1.objective).reshape(-1)
    ob4 = np.asarray(r4.objective).reshape(-1)
    log(f"  objectives one device {ob1.tolist()}")
    log(f"  objectives sharded    {ob4.tolist()}")
    log(f"  bitwise equal: iterations {bool((it1 == it4).all())}, "
        f"objectives {bool((ob1 == ob4).all())}")
    check(bool(np.asarray(r4.converged).all()),
          f"sharded: every lane converged to eps={EPS:g}")
    check(bool((it1 == it4).all()),
          "sharded iteration counts equal the single-device engine")
    check(float(rel(ob4, ob1).max()) <= OBJ_REL_SHARDED,
          "sharded objectives equal the single-device engine")


# ---------------------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of every generated dataset")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the lane-sharded grid over 4 chips")
    args = ap.parse_args()

    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (first device: {devs[0].platform}); "
              f"nothing to check", file=sys.stderr)
        return 2
    if args.four_chips and len(devs) < 4:
        print(f"chip_smoke: --four-chips needs 4 chips, found {len(devs)}",
              file=sys.stderr)
        return 2

    from repro.kernels import ops
    from repro.runtime.compile_cache import enable_compile_cache

    cache = enable_compile_cache(ROOT)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    log(f"devices: {device}; jax {jax.__version__}; x64 "
        f"{jax.config.jax_enable_x64}; compile cache {cache}")
    if jax.config.jax_enable_x64:
        print("chip_smoke: chip runs are float32 — unset JAX_ENABLE_X64",
              file=sys.stderr)
        return 2
    impl = "auto"
    resolved = ops.resolve_impl(impl)
    log(f"impl='auto' resolves to {resolved!r}")
    if resolved != "pallas":
        print("chip_smoke: impl='auto' did not pick the Pallas kernels",
              file=sys.stderr)
        return 1

    phases = ([("four chips", four_chips)] if args.four_chips else
              [("phase 1", phase1), ("phase 2a", phase2a),
               ("phase 2b", phase2b)])
    t_all = time.perf_counter()
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            fn(args.seed, impl)
        except Failed as e:
            print(f"chip_smoke: {name} failed: {e}", file=sys.stderr)
            return 1
        log(f"{name}: wall {time.perf_counter() - t0:.3f} s")
    log(f"all phases passed in {time.perf_counter() - t_all:.3f} s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
