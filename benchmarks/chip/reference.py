"""Plain reference for the RBF SVM dual: the optimality check of a solve.

Every lane of a job is the LIBSVM dual in signed form

    min_a  1/2 a' K a - y' a   s.t.  sum(a) = 0,
           L_i = min(0, y_i C) <= a_i <= U_i = max(0, y_i C),
    K_ij = exp(-gamma |x_i - x_j|^2),

and a solve returns ``a`` and the bias ``b``.  The reference recomputes
the gradient ``G = y - K a`` from the data and the returned ``a`` alone,
in row blocks on the default device, in float32 with every matmul at
``HIGHEST`` precision, and then again in float64 on the host for the
rows that can decide each lane's gap (the largest G over ``a_i < U_i``,
the smallest over ``a_i > L_i``), so the numbers below are float64 sums
wherever they are decided.  The float32 pass alone is not enough: at
C = 8 the terms of ``K a`` sum to some 1e5 in magnitude while ``G`` is
of order 1, and float32 loses up to about 4e-3 of it.  From that exact
``G`` it gives, per lane:

* ``gap``: the KKT gap ``max_{a_i < U_i} G_i - min_{a_i > L_i} G_i``
  (LIBSVM's stopping quantity, 0 where either side is empty);
* ``b_ref``: the bias a solver at this ``a`` would report, the midpoint
  of those two ends (the surviving end where the other side is empty);
* ``box``: how far ``a`` lies outside its box, which must be 0 exactly;
* ``ref_err``: how far the float32 pass was off on the refined rows;
* ``refined``: how many rows of each lane were redone in float64, and
  ``capped``, whether a side reached ``MAX_REFINE`` rows (the gap then
  reads at most the float32 error short of the true one).

It imports nothing of the program and takes nothing that the program
made: the lanes' labels, C and gamma come from the benchmark's own reading
of the job (``entries/*.problems``).
"""

from __future__ import annotations

import numpy as np

BLOCK = 4096          # rows of K per block: BLOCK x l floats on the device
REFINE = 32           # rows per side of each lane's gap redone in float64
MAX_REFINE = 16384    # at most this many per side
CHUNK = 1024          # rows per float64 block on the host


def _block_fn():
    import jax
    import jax.numpy as jnp
    hi = jax.lax.Precision.HIGHEST

    @jax.jit
    def kalpha(xb, sqb, X, sq, A, gamma):
        d2 = (sqb[:, None] + sq[None, :]
              - 2.0 * jnp.dot(xb, X.T, precision=hi))
        K = jnp.exp(-gamma * jnp.maximum(d2, 0.0))
        return jnp.dot(K, A.T, precision=hi)          # (block, lanes)
    return kalpha


def exact_gradient(X: np.ndarray, labels: np.ndarray, gamma: np.ndarray,
                   alpha: np.ndarray) -> np.ndarray:
    """``G = y - K a`` per lane, (B, l) float64, from float32 blocks."""
    import jax.numpy as jnp
    kalpha = _block_fn()
    l = X.shape[0]
    nb = -(-l // BLOCK)
    Xp = np.zeros((nb * BLOCK, X.shape[1]), np.float32)
    Xp[:l] = X
    Xd = jnp.asarray(X, jnp.float32)
    sq = jnp.sum(Xd * Xd, axis=1)
    Xpd = jnp.asarray(Xp)
    sqp = jnp.sum(Xpd * Xpd, axis=1)
    G = np.empty(alpha.shape, np.float64)
    for g in np.unique(gamma):
        lanes = np.flatnonzero(gamma == g)
        A = jnp.asarray(alpha[lanes], jnp.float32)
        g32 = jnp.float32(g)
        parts = [np.asarray(kalpha(Xpd[i * BLOCK:(i + 1) * BLOCK],
                                   sqp[i * BLOCK:(i + 1) * BLOCK],
                                   Xd, sq, A, g32), np.float64)
                 for i in range(nb)]
        KA = np.concatenate(parts, axis=0)[:l].T      # (lanes, l)
        G[lanes] = labels[lanes].astype(np.float64) - KA
    return G


def refine(X, labels, gamma, alpha, G, up, dn):
    """Redo in float64, in place, the rows of ``G`` that decide each
    lane's gap.  Returns each lane's largest change there (the float32
    pass's error), the (B, l) mask of rows redone and, per lane, whether
    a side reached ``MAX_REFINE`` rows.

    First the ``REFINE`` rows nearest each end of the gap by the float32
    values; then every row whose float32 value lies within twice the
    error seen so far of the float64 end, so that no row the float32 pass
    misranked can hold the true end.
    """
    X64 = np.asarray(X, np.float64)
    sq = np.einsum("ij,ij->i", X64, X64)
    err = np.zeros(G.shape[0])
    done = np.zeros(G.shape, bool)
    capped = np.zeros(G.shape[0], bool)
    for k in range(G.shape[0]):
        g32 = G[k].copy()
        a64 = np.asarray(alpha[k], np.float64)

        def redo(rows):
            rows = rows[~done[k, rows]]
            for c in range(0, rows.size, CHUNK):
                r = rows[c:c + CHUNK]
                d2 = np.maximum(sq[r, None] + sq[None, :]
                                - 2.0 * X64[r] @ X64.T, 0.0)
                G[k, r] = labels[k, r] - np.exp(-gamma[k] * d2) @ a64
                err[k] = max(err[k], np.abs(G[k, r] - g32[r]).max())
            done[k, rows] = True
            return rows.size

        u, d = np.flatnonzero(up[k]), np.flatnonzero(dn[k])
        best_u = u[np.argsort(-g32[u])][:MAX_REFINE]   # by float32 value
        best_d = d[np.argsort(g32[d])][:MAX_REFINE]
        redo(np.union1d(best_u[:REFINE], best_d[:REFINE]))
        while True:
            slack = 2.0 * err[k]
            near_u = best_u[g32[best_u]
                            >= G[k, u].max(initial=-np.inf) - slack]
            near_d = best_d[g32[best_d]
                            <= G[k, d].min(initial=np.inf) + slack]
            capped[k] |= (near_u.size == MAX_REFINE < u.size
                          or near_d.size == MAX_REFINE < d.size)
            if redo(np.union1d(near_u, near_d)) == 0:
                break
    return err, done, capped


def check(X, labels, C, gamma, alpha, b) -> dict[str, np.ndarray]:
    """Per-lane ``gap``, ``b_ref``, ``bias_err``, ``box``, ``ref_err``,
    ``refined`` and ``capped`` of a solve.

    ``labels`` (B, l) are +-1, ``C`` and ``gamma`` (B,), ``alpha`` (B, l)
    and ``b`` (B,) are what the job returned.
    """
    labels = np.asarray(labels, np.float32)
    alpha = np.asarray(alpha, np.float32)
    C32 = np.asarray(C, np.float32)[:, None]
    U = np.maximum(np.float32(0), labels * C32)
    L = np.minimum(np.float32(0), labels * C32)
    gamma = np.asarray(gamma, np.float64)
    G = exact_gradient(X, labels, gamma, alpha)
    up, dn = alpha < U, alpha > L
    ref_err, done, capped = refine(X, labels, gamma, alpha, G, up, dn)
    g_up = np.where(up, G, -np.inf).max(axis=1)
    g_dn = np.where(dn, G, np.inf).min(axis=1)
    both = np.isfinite(g_up) & np.isfinite(g_dn)
    gap = np.where(both, g_up - g_dn, 0.0)
    b_ref = np.where(both, 0.5 * (g_up + g_dn),
                     np.where(np.isfinite(g_up), g_up,
                              np.where(np.isfinite(g_dn), g_dn, 0.0)))
    box = np.maximum(np.maximum(alpha - U, L - alpha), 0).max(axis=1)
    return {"gap": gap, "b_ref": b_ref,
            "bias_err": np.abs(np.asarray(b, np.float64) - b_ref),
            "box": box.astype(np.float64), "ref_err": ref_err,
            "refined": done.sum(axis=1), "capped": capped}
