"""Tiled RBF Gram-matrix Pallas kernel (MXU matmul + fused exp epilogue).

2D grid over (BI, BJ) output tiles.  Each step loads one (BI, d) and one
(BJ, d) tile of the inputs, runs the (BI, d) x (d, BJ) contraction on the
MXU with f32 accumulation, and applies the squared-distance + exp epilogue
on the VPU before a single HBM write of the tile — the distance matrix is
never materialized.  Used by batch/precompute mode, the SVM probe head and
the cross-kernel at prediction time.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ref import HIGHEST


def _kernel(g_ref, X1_ref, X2_ref, s1_ref, s2_ref, out_ref):
    gamma = g_ref[0, 0]
    x1 = X1_ref[...]                       # (BI, d)
    x2 = X2_ref[...]                       # (BJ, d)
    prod = jax.lax.dot_general(
        x1, x2, (((1,), (1,)), ((), ())), precision=HIGHEST,
        preferred_element_type=jnp.promote_types(x1.dtype, jnp.float32))
    d2 = s1_ref[...].T + s2_ref[...] - 2.0 * prod   # (BI, BJ)
    out_ref[...] = jnp.exp(-gamma * jnp.maximum(d2, 0.0)).astype(
        out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_i", "block_j",
                                             "interpret"))
def gram_pallas(X1, X2, s1, s2, gamma, *, block_i: int = 256,
                block_j: int = 256, interpret: bool = False):
    """Cross Gram matrix k(X1, X2): (l1, l2).  Inputs padded to block
    multiples by the ops wrapper; padded rows give harmless extra entries
    that the wrapper slices off."""
    l1, d = X1.shape
    l2, _ = X2.shape
    assert l1 % block_i == 0 and l2 % block_j == 0
    out = pl.pallas_call(
        _kernel,
        grid=(l1 // block_i, l2 // block_j),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),           # gamma
            pl.BlockSpec((block_i, d), lambda i, j: (i, 0)),     # X1
            pl.BlockSpec((block_j, d), lambda i, j: (j, 0)),     # X2
            pl.BlockSpec((1, block_i), lambda i, j: (0, i)),     # s1
            pl.BlockSpec((1, block_j), lambda i, j: (0, j)),     # s2
        ],
        out_specs=pl.BlockSpec((block_i, block_j), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((l1, l2), X1.dtype),
        interpret=interpret,
    )(jnp.asarray(gamma, X1.dtype).reshape(1, 1), X1, X2,
      s1.reshape(1, l1), s2.reshape(1, l2))
    return out
