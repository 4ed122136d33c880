"""Distributed PA-SMO: the example dimension ℓ sharded over a mesh axis.

This is how SMO actually runs on a pod (DESIGN.md §3): the training set X,
the dual variables alpha and the gradient G live sharded over the ``data``
axis.  SMO's minimal working set is exactly what makes it distributable —
per iteration the collectives are:

  1. all_gather of P (value, index) candidates for the first-order i-pick,
  2. one psum broadcasting x_i plus O(1) scalars      (payload d + 3),
  3. all_gather of P j-candidates (WSS2 second-order),
  4. one psum broadcasting x_j plus O(1) scalars      (payload d + 3),
  5. one psum fetching O(1) gradient entries for planning / Alg. 3,
  6. one pmax/pmin pair for the KKT stopping gap      (payload 2).

Everything else — the two kernel-row blocks, the gradient update, the
masked reductions — is embarrassingly parallel over ℓ/P local rows.  All
O(1) cross terms (the ≤4x4 principal minor of K the paper's planning step
needs) are computed locally from *replicated* support-point vectors
(x_i, x_j and the previous working set's x's), so planning-ahead adds ZERO
extra collectives — the paper's O(1)-per-step property survives sharding.

RBF kernel only (the paper's experimental setting); the oracle diag is 1.
The padded tail (to make ℓ divisible by the axis size) gets L = U = 0 so it
can never enter a working set.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.qp import TAU
from repro.core import step as step_mod
from repro.core.solver import SolverConfig


class ShardedResult(NamedTuple):
    alpha: jax.Array       # (l_padded,) sharded
    iterations: jax.Array
    objective: jax.Array
    kkt_gap: jax.Array
    converged: jax.Array
    n_planning: jax.Array
    b: jax.Array


def _pad_to(x, n, value=0.0):
    pad = n - x.shape[0]
    if pad == 0:
        return x
    widths = [(0, pad)] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, widths, constant_values=value)


def solve_sharded(X, y, C, gamma, mesh: Mesh, cfg: SolverConfig,
                  axis: str = "data") -> ShardedResult:
    """Solve the dual SVM QP with ℓ sharded over ``mesh[axis]``.

    Supports algorithm in {"smo", "pasmo"} with plan_candidates == 1.
    """
    assert cfg.algorithm in ("smo", "pasmo")
    assert cfg.plan_candidates == 1, "sharded path implements N=1"
    Pn = mesh.shape[axis]
    l, d = X.shape
    lp = ((l + Pn - 1) // Pn) * Pn
    X = _pad_to(jnp.asarray(X), lp)
    y = _pad_to(jnp.asarray(y), lp)  # padded labels 0 -> L = U = 0
    dtype = X.dtype
    C = jnp.asarray(C, dtype)
    gamma = jnp.asarray(gamma, dtype)
    eps = cfg.eps
    eta = cfg.eta
    planning = cfg.algorithm == "pasmo"

    nloc = lp // Pn

    def rbf_block(Xl, sql, xq):
        """Local kernel-row block k(x_q, X_local)."""
        d2 = jnp.dot(xq, xq) + sql - 2.0 * (Xl @ xq)
        return jnp.exp(-gamma * jnp.maximum(d2, 0.0))

    def local_solve(Xl, yl):
        me = jax.lax.axis_index(axis)
        offset = me * nloc
        gidx = offset + jnp.arange(nloc, dtype=jnp.int32)
        sql = jnp.sum(Xl * Xl, axis=-1)
        Ll = jnp.minimum(0.0, yl * C)
        Ul = jnp.maximum(0.0, yl * C)

        def fetch(vec, g):
            """Replicate vec[g] (global index) to all shards."""
            lidx = g % nloc
            mine = (g // nloc) == me
            return jax.lax.psum(
                jnp.where(mine, jnp.take(vec, lidx), 0.0), axis)

        def bcast_point(g, alpha):
            """Replicate (x_g, alpha_g, y_g) in one psum of (d+2,)."""
            lidx = g % nloc
            mine = (g // nloc) == me
            row = jnp.where(mine, jnp.take(Xl, lidx, axis=0),
                            jnp.zeros((d,), dtype))
            sc = jnp.where(mine,
                           jnp.stack([jnp.take(alpha, lidx),
                                      jnp.take(yl, lidx)]),
                           jnp.zeros((2,), dtype))
            out = jax.lax.psum(jnp.concatenate([row, sc]), axis)
            return out[:d], out[d], out[d + 1]

        def global_argmax(val_loc, idx_loc):
            vals = jax.lax.all_gather(val_loc, axis)   # (P,)
            idxs = jax.lax.all_gather(idx_loc.astype(jnp.int32), axis)
            w = jax.lax.argmax(vals, 0, jnp.int32)
            return jnp.take(idxs, w), jnp.take(vals, w)

        class Carry(NamedTuple):
            alpha: jax.Array
            G: jax.Array
            t: jax.Array
            done: jax.Array
            gap: jax.Array
            # previous / prev-prev working sets: global ids + replicated x
            pi: jax.Array
            pj: jax.Array
            qi: jax.Array
            qj: jax.Array
            x_pi: jax.Array
            x_pj: jax.Array
            x_qi: jax.Array
            x_qj: jax.Array
            n_hist: jax.Array
            p_smo: jax.Array
            prev_free: jax.Array
            prev_ratio_ok: jax.Array
            n_planning: jax.Array

        def body(c: Carry) -> Carry:
            alpha, G = c.alpha, c.G
            up = alpha < Ul
            dn = alpha > Ll

            # ---- i selection (first-order part of WSS2) -------------------
            vi = jnp.where(up, G, -jnp.inf)
            li = jax.lax.argmax(vi, 0, jnp.int32)
            i_g, g_i = global_argmax(jnp.take(vi, li), offset + li)
            x_i, a_i, y_i = bcast_point(i_g, alpha)
            L_i = jnp.minimum(0.0, y_i * C)
            U_i = jnp.maximum(0.0, y_i * C)
            k_i = rbf_block(Xl, sql, x_i)

            # ---- j selection ----------------------------------------------
            use_exact = planning & (~c.p_smo) & (~c.prev_ratio_ok)
            lvec = g_i - G
            qvec = jnp.maximum(1.0 - 2.0 * k_i + 1.0, TAU)  # RBF diag = 1
            g_tilde = 0.5 * lvec * lvec / qvec
            lo_v = jnp.maximum(L_i - a_i, alpha - Ul)
            hi_v = jnp.minimum(U_i - a_i, alpha - Ll)
            mu_v = jnp.clip(lvec / qvec, lo_v, hi_v)
            g_exact = lvec * mu_v - 0.5 * qvec * mu_v * mu_v
            gains = jnp.where(use_exact, g_exact, g_tilde)
            cand = dn & (lvec > 0) & (gidx != i_g)
            vj = jnp.where(cand, gains, -jnp.inf)
            lj = jax.lax.argmax(vj, 0, jnp.int32)
            j_g, best_gain = global_argmax(jnp.take(vj, lj), offset + lj)

            # ---- Alg. 3 extra candidate B^(t-2) ----------------------------
            # O(1) gradient entries for the candidate and for planning, one
            # fused psum: [G_pi, G_pj, G_qi, G_qj, a_qi, a_qj]
            fetch_idx = jnp.stack([c.pi, c.pj, c.qi, c.qj])
            lidx = fetch_idx % nloc
            mine = (fetch_idx // nloc) == me
            gvals = jax.lax.psum(
                jnp.where(mine, jnp.take(G, lidx), 0.0), axis)
            avals = jax.lax.psum(
                jnp.where(mine[2:], jnp.take(alpha, lidx[2:]), 0.0), axis)
            G_pi, G_pj, G_qi, G_qj = gvals[0], gvals[1], gvals[2], gvals[3]
            a_qi, a_qj = avals[0], avals[1]

            i_sel, j_sel = i_g, j_g
            if planning:
                y_qi = fetch(yl, c.qi)
                y_qj = fetch(yl, c.qj)
                K_qq = jnp.exp(-gamma * jnp.maximum(
                    jnp.sum((c.x_qi - c.x_qj) ** 2), 0.0))
                l_q = G_qi - G_qj
                q_q = jnp.maximum(2.0 - 2.0 * K_qq, TAU)
                lo_q = jnp.maximum(jnp.minimum(0.0, y_qi * C) - a_qi,
                                   a_qj - jnp.maximum(0.0, y_qj * C))
                hi_q = jnp.minimum(jnp.maximum(0.0, y_qi * C) - a_qi,
                                   a_qj - jnp.minimum(0.0, y_qj * C))
                mu_q = jnp.clip(l_q / q_q, lo_q, hi_q)
                cg_exact = l_q * mu_q - 0.5 * q_q * mu_q * mu_q
                cg_tilde = 0.5 * l_q * l_q / q_q
                cg = jnp.where(use_exact, cg_exact, cg_tilde)
                adm = ((a_qi < jnp.maximum(0.0, y_qi * C))
                       & (a_qj > jnp.minimum(0.0, y_qj * C))
                       & (l_q > 0) & (c.qi != c.qj) & (c.n_hist > 1))
                take = (~c.p_smo) & adm & (cg > best_gain)
                i_sel = jnp.where(take, c.qi, i_g)
                j_sel = jnp.where(take, c.qj, j_g)
            else:
                take = jnp.asarray(False)

            # replicated data of the selected pair
            x_i2, a_i2, y_i2 = bcast_point(i_sel, alpha)
            x_j2, a_j2, y_j2 = bcast_point(j_sel, alpha)
            k_i2 = rbf_block(Xl, sql, x_i2)
            k_j2 = rbf_block(Xl, sql, x_j2)
            G_i2 = jnp.where(take, G_qi, g_i)
            G_j2 = fetch(G, j_sel)

            # ---- step (Alg. 4 / eq. 2) -------------------------------------
            L_i2 = jnp.minimum(0.0, y_i2 * C)
            U_i2 = jnp.maximum(0.0, y_i2 * C)
            L_j2 = jnp.minimum(0.0, y_j2 * C)
            U_j2 = jnp.maximum(0.0, y_j2 * C)
            lw = G_i2 - G_j2
            K_ij = jnp.exp(-gamma * jnp.maximum(
                jnp.sum((x_i2 - x_j2) ** 2), 0.0))
            q11 = jnp.maximum(2.0 - 2.0 * K_ij, TAU)
            sb = step_mod.step_bounds(a_i2, a_j2, L_i2, U_i2, L_j2, U_j2)
            mu_star = lw / q11
            mu_smo, free_smo = step_mod.smo_step(lw, q11, sb)

            do_plan = jnp.asarray(False)
            mu_plan = mu_smo
            ratio_ok = c.prev_ratio_ok
            if planning:
                # all 2x2 cross terms local thanks to replicated x vectors
                def k(xa, xb):
                    return jnp.exp(-gamma * jnp.maximum(
                        jnp.sum((xa - xb) ** 2), 0.0))

                w2 = G_pi - G_pj
                q22 = jnp.maximum(2.0 - 2.0 * k(c.x_pi, c.x_pj), TAU)
                q12 = (k(x_i2, c.x_pi) - k(x_i2, c.x_pj)
                       - k(x_j2, c.x_pi) + k(x_j2, c.x_pj))
                terms = step_mod.PlanningTerms(w1=lw, w2=w2, Q11=q11,
                                               Q22=q22, Q12=q12)
                mu1, okdet = step_mod.planning_step(terms)
                mu2 = step_mod.planned_second_step(mu1, terms)
                interior1 = (sb.lo < mu1) & (mu1 < sb.hi)
                y_pi = fetch(yl, c.pi)
                y_pj = fetch(yl, c.pj)
                a_pi = fetch(alpha, c.pi) + mu1 * (
                    (c.pi == i_sel).astype(dtype)
                    - (c.pi == j_sel).astype(dtype))
                a_pj = fetch(alpha, c.pj) + mu1 * (
                    (c.pj == i_sel).astype(dtype)
                    - (c.pj == j_sel).astype(dtype))
                sb2 = step_mod.step_bounds(
                    a_pi, a_pj,
                    jnp.minimum(0.0, y_pi * C), jnp.maximum(0.0, y_pi * C),
                    jnp.minimum(0.0, y_pj * C), jnp.maximum(0.0, y_pj * C))
                interior2 = (sb2.lo < mu2) & (mu2 < sb2.hi)
                feasible = okdet & interior1 & interior2 & (c.n_hist > 0)
                do_plan = c.prev_free & feasible
                mu_plan = jnp.where(do_plan, mu1, mu_smo)
                ratio = mu1 / jnp.where(jnp.abs(mu_star) > 0, mu_star, 1.0)
                ratio_ok = jnp.where(do_plan,
                                     (ratio >= 1.0 - eta)
                                     & (ratio <= 1.0 + eta),
                                     c.prev_ratio_ok)

            mu = jnp.where(do_plan, mu_plan, mu_smo)

            # ---- update -----------------------------------------------------
            sel_vec = ((gidx == i_sel).astype(dtype)
                       - (gidx == j_sel).astype(dtype))
            alpha_new = alpha + mu * sel_vec
            G_new = G - mu * (k_i2 - k_j2)

            # ---- stopping ---------------------------------------------------
            up2 = alpha_new < Ul
            dn2 = alpha_new > Ll
            g_up = jax.lax.pmax(
                jnp.max(jnp.where(up2, G_new, -jnp.inf)), axis)
            g_dn = -jax.lax.pmax(
                jnp.max(jnp.where(dn2, -G_new, -jnp.inf)), axis)
            gap = g_up - g_dn

            return Carry(
                alpha=alpha_new, G=G_new, t=c.t + 1, done=gap <= eps,
                gap=gap,
                pi=i_sel, pj=j_sel, qi=c.pi, qj=c.pj,
                x_pi=x_i2, x_pj=x_j2, x_qi=c.x_pi, x_qj=c.x_pj,
                n_hist=jnp.minimum(c.n_hist + 1, 2),
                p_smo=~do_plan,
                prev_free=(~do_plan) & free_smo,
                prev_ratio_ok=ratio_ok,
                n_planning=c.n_planning + do_plan.astype(jnp.int32))

        alpha0 = jnp.zeros((nloc,), dtype)
        G0 = yl
        up0 = alpha0 < Ul
        dn0 = alpha0 > Ll
        g_up0 = jax.lax.pmax(jnp.max(jnp.where(up0, G0, -jnp.inf)), axis)
        g_dn0 = -jax.lax.pmax(jnp.max(jnp.where(dn0, -G0, -jnp.inf)), axis)
        zero_i = jnp.asarray(0, jnp.int32)
        zd = jnp.zeros((d,), dtype)
        c0 = Carry(alpha=alpha0, G=G0, t=zero_i,
                   done=(g_up0 - g_dn0) <= eps, gap=g_up0 - g_dn0,
                   pi=zero_i, pj=zero_i, qi=zero_i, qj=zero_i,
                   x_pi=zd, x_pj=zd, x_qi=zd, x_qj=zd,
                   n_hist=zero_i,
                   p_smo=jnp.asarray(True), prev_free=jnp.asarray(False),
                   prev_ratio_ok=jnp.asarray(True),
                   n_planning=zero_i)

        c = jax.lax.while_loop(
            lambda c: (~c.done) & (c.t < cfg.max_iter), body, c0)

        # finalize: objective f = 1/2 (y.a + G.a) (local dot + psum)
        obj = jax.lax.psum(0.5 * (jnp.dot(yl, c.alpha)
                                  + jnp.dot(c.G, c.alpha)), axis)
        up = c.alpha < Ul
        dn = c.alpha > Ll
        g_up = jax.lax.pmax(jnp.max(jnp.where(up, c.G, -jnp.inf)), axis)
        g_dn = -jax.lax.pmax(jnp.max(jnp.where(dn, -c.G, -jnp.inf)), axis)
        b = 0.5 * (g_up + g_dn)
        return (c.alpha, c.t, obj, c.gap, c.done, c.n_planning, b)

    spec_l = P(axis)
    out = jax.jit(jax.shard_map(
        local_solve, mesh=mesh,
        in_specs=(P(axis, None), spec_l),
        out_specs=(spec_l, P(), P(), P(), P(), P(), P()),
        check_vma=False))(X, y)
    return ShardedResult(*out)
