"""RowSource: the one abstraction for how pass A/B obtain kernel rows.

The fused two-pass engine needs, per iteration, the kernel rows of the two
working-set coordinates plus a handful of O(1) entries.  Three structurally
different suppliers exist:

* **rbf** — rows are recomputed from the shared ``X`` (the accelerator
  memory mode: no Gram is ever materialized);
* **rbf, doubled** — the ε-SVR operator: the lane state has 2l variables
  but row k of ``Q = [[K, K], [K, K]]`` is the *base* row tiled, so every
  row/entry folds its index onto the base axis (``k mod l``) and the O(l d)
  work never doubles;
* **bank** — a shared ``(n_stack, l, l)`` base Gram bank plus a per-lane
  stack index: rows become gathers and the exp work is paid once per
  distinct gamma instead of per iteration (the CPU throughput mode — and,
  via the rows-variant Pallas kernels, available on the
  ``interpret``/``pallas`` backends too).

A :class:`RowSource` is a pytree (jit-transparent; ``dup`` is static) and
is consumed by the dispatchers in :mod:`repro.kernels.ops`
(:func:`~repro.kernels.ops.source_row_wss` /
:func:`~repro.kernels.ops.source_update_wss`) — one call site in the
solver regardless of supplier or backend.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.ref import HIGHEST, rbf_exp


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=("X", "sqn", "gammas", "gram", "gram_idx", "xt", "xt_sqn"),
    meta_fields=("dup",))
@dataclasses.dataclass(frozen=True)
class RowSource:
    """Where pass A/B kernel rows come from (see module docstring).

    Exactly one of (``X``, ``sqn``) / (``gram``, ``gram_idx``) supplies the
    rows; ``gammas`` is the (B,) per-lane RBF width (used by the rbf
    supplier and by :meth:`entry_pairs`).  ``dup`` marks the doubled ε-SVR
    operator: lane state indices live in [0, 2l) and fold onto the base
    example axis through :meth:`base_idx`.  ``xt``/``xt_sqn`` are the
    stream the Pallas passes read: X feature-major and zero-padded, and
    its norms padded alike, built once per engine call by
    :func:`repro.kernels.ops.pad_source`; ``X``/``sqn`` stay unpadded
    for the gathers, the O(1) entries and the exit check.
    """

    X: Optional[jax.Array] = None          # (l, d) base inputs
    sqn: Optional[jax.Array] = None        # (l,) squared norms
    gammas: Optional[jax.Array] = None     # (B,) per-lane RBF widths
    gram: Optional[jax.Array] = None       # (n_stack, l, l) base Gram bank
    gram_idx: Optional[jax.Array] = None   # (B,) lane -> stack entry
    xt: Optional[jax.Array] = None         # (d8, lpad) feature-major X
    xt_sqn: Optional[jax.Array] = None     # (lpad,) norms of xt's columns
    dup: bool = False

    # -- static structure ---------------------------------------------------

    @property
    def is_bank(self) -> bool:
        return self.gram is not None

    @property
    def base_l(self) -> int:
        """True base example count (never the padded or doubled length)."""
        if self.is_bank:
            return self.gram.shape[-1]
        return self.X.shape[0]

    @property
    def n(self) -> int:
        """Lane-state width: 2l for the doubled operator, else l."""
        return self.base_l * (2 if self.dup else 1)

    # -- index folding / gathers --------------------------------------------

    def base_idx(self, idx):
        """Fold a (possibly doubled) coordinate index onto the base axis."""
        return idx % self.base_l if self.dup else idx

    def query(self, idx):
        """Per-lane pass inputs at (stacked) coordinate indices ``idx``.

        Bank: the gathered (m, l) *base* rows.  Rbf: the (m, d) base query
        rows plus their squared norms.  Tiling for the doubled operator
        happens downstream (in-kernel, or in the jnp oracle) — never here.
        """
        b = self.base_idx(idx)
        if self.is_bank:
            reps = idx.shape[0] // self.gram_idx.shape[0]
            return self.gram[jnp.tile(self.gram_idx, reps), b]
        return jnp.take(self.X, b, axis=0), jnp.take(self.sqn, b)

    def entry_pairs(self, a, b, reps: int):
        """O(1) kernel entries for ``reps`` stacked (reps*B,) index pairs."""
        if self.is_bank:
            return self.gram[jnp.tile(self.gram_idx, reps),
                             self.base_idx(a), self.base_idx(b)]
        a, b = self.base_idx(a), self.base_idx(b)
        d2 = (jnp.take(self.sqn, a) + jnp.take(self.sqn, b)
              - 2.0 * jnp.sum(jnp.take(self.X, a, axis=0)
                              * jnp.take(self.X, b, axis=0), axis=-1))
        return rbf_exp(-jnp.tile(self.gammas, reps) * jnp.maximum(d2, 0.0))

    def matvec(self, v, block: int = 256):
        """Per-lane operator matvec ``Q_b v_b`` for a (B, n) stack.

        Backs the LIBSVM-style gradient reconstruction
        ``G = p - Q alpha`` after hard shrinking (see
        :func:`repro.core.solver_fused.solve_fused_chunked_qp`).  The
        doubled operator folds its halves first (``Q v = tile(K (v+ +
        v-))``) so the contraction always runs at base width; the rbf
        supplier blocks over rows of X like
        :meth:`repro.core.qp.RBFKernel.matvec` but with per-lane gammas.
        """
        l = self.base_l
        if self.dup:
            v = v[:, :l] + v[:, l:]
        if self.is_bank:
            mv = jnp.einsum("sij,bj->sbi", self.gram, v,
                            precision=HIGHEST)
            out = mv[self.gram_idx,
                     jnp.arange(v.shape[0], dtype=jnp.int32)]
        else:
            X, sqn = self.X, self.sqn
            d = X.shape[1]
            pad = (-l) % block
            Xp = jnp.pad(X, ((0, pad), (0, 0)))
            sp = jnp.pad(sqn, (0, pad))

            def blk(args):
                Xb, nb = args
                d2 = (nb[:, None] + sqn[None, :]
                      - 2.0 * jnp.dot(Xb, X.T, precision=HIGHEST))
                k = rbf_exp(-self.gammas[:, None, None]
                            * jnp.maximum(d2, 0.0)[None])    # (B, block, l)
                return jnp.einsum("bkl,bl->bk", k, v, precision=HIGHEST)

            out = jax.lax.map(blk, (Xp.reshape(-1, block, d),
                                    sp.reshape(-1, block)))
            out = jnp.moveaxis(out, 0, 1).reshape(v.shape[0], -1)[:, :l]
        return jnp.concatenate([out, out], axis=1) if self.dup else out

    def exact_rows(self, rows, v):
        """Entries ``rows`` (B, m) of each lane's ``Q v`` for a (B, n)
        stack ``v``, accurate to well below the solver's eps in float32.

        Backs the fused engine's exit check, which recomputes the gradient
        ``G = p - Q alpha`` of the rows that decide a lane's gap.  The
        kernel entries take :func:`~repro.kernels.ref.rbf_exp`, and their
        distances are taken about the column mean of X, which the kernel
        does not see, so their rounding scales with ``|x - mean|^2``
        rather than with the raw squared norms.  Each block of
        :data:`EXACT_BLOCK` columns is contracted at ``HIGHEST`` and the
        block sums are added with a compensated (two-sum) accumulation, so
        the error does not grow with l and at most ``m x EXACT_BLOCK``
        entries of a lane exist at once.  The doubled operator folds the
        halves of ``v`` as :meth:`matvec` does; a Gram bank reads the rows.
        """
        l = self.base_l
        if self.dup:
            v = v[:, :l] + v[:, l:]
        r = self.base_idx(rows)
        m = r.shape[1]
        if self.is_bank:
            def lane_rows(K, v):
                return _block_sum(
                    lambda c, w: jax.lax.dynamic_slice_in_dim(K, c, w, 1),
                    v, m)
            return jax.vmap(lambda k, r, v: lane_rows(self.gram[k][r], v))(
                self.gram_idx, r, v)
        mean = jnp.sum(self.X, axis=0) / l

        def lane_rows(r, v, gamma):
            xq = jnp.take(self.X, r, axis=0) - mean
            sqq = jnp.sum(xq * xq, axis=-1)

            def kblock(c, w):
                xb = jax.lax.dynamic_slice_in_dim(self.X, c, w, 0) - mean
                d2 = (sqq[:, None] + jnp.sum(xb * xb, axis=-1)[None, :]
                      - 2.0 * jnp.dot(xq, xb.T, precision=HIGHEST))
                return rbf_exp(-gamma * jnp.maximum(d2, 0.0))

            return _block_sum(kblock, v, m)

        return jax.vmap(lane_rows)(r, v, self.gammas)


EXACT_BLOCK = 1024     # columns per block of an exact row sum


def _block_sum(kblock, v, m: int):
    """``sum_j kblock_j * v_j`` for each of ``m`` rows: blocks of
    :data:`EXACT_BLOCK` columns (``kblock(c, w)`` gives the (m, w) entries
    of columns ``[c, c + w)``, ``w`` static), each contracted at
    ``HIGHEST``, their sums added with Knuth's two-sum and the rounding
    errors carried apart."""
    w = min(EXACT_BLOCK, v.shape[0])
    nb, tail = divmod(v.shape[0], w)

    def add(c, w_, acc):
        s, e = acc
        part = jnp.dot(kblock(c, w_), jax.lax.dynamic_slice_in_dim(v, c, w_),
                       precision=HIGHEST)
        t = s + part
        bb = t - s
        return t, e + ((s - (t - bb)) + (part - bb))

    zero = jnp.zeros((m,), v.dtype)
    acc = jax.lax.fori_loop(
        jnp.int32(0), jnp.int32(nb),
        lambda b, acc: add(b * jnp.int32(w), w, acc), (zero, zero))
    if tail:
        acc = add(nb * w, tail, acc)
    return acc[0] + acc[1]


def rbf_source(X, gammas, B: int, *, dup: bool = False) -> RowSource:
    """Row source recomputing rows from the shared ``X`` (l, d)."""
    X = jnp.asarray(X)
    gammas = jnp.broadcast_to(jnp.asarray(gammas, X.dtype), (B,))
    return RowSource(X=X, sqn=jnp.sum(X * X, axis=-1), gammas=gammas,
                     dup=dup)


def bank_source(gram, gram_idx, gammas=None, *, dup: bool = False
                ) -> RowSource:
    """Row source gathering rows from a shared base Gram bank."""
    gram = jnp.asarray(gram)
    gram_idx = jnp.asarray(gram_idx, jnp.int32)
    if gammas is not None:
        gammas = jnp.broadcast_to(jnp.asarray(gammas, gram.dtype),
                                  gram_idx.shape)
    return RowSource(gram=gram, gram_idx=gram_idx, gammas=gammas, dup=dup)
