"""Ahead-of-time compiles of the main path for a described TPU v5e chip.

Interpret mode cannot see what the chip's compiler refuses: block shapes
off the (8, 128) tiling, kernels that overflow the scoped VMEM, programs
that do not fit the device.  These tests lower the batched pass A / pass B
kernels (through the ops wrappers, so the tile planner is exercised) and
the whole fused engine at real widths for one chip of a described
``v5e:2x2`` topology, and compile them.  Nothing runs; a compile that
passes is not a chip run.

The topology is described inside a module fixture (never at import: only
one process may hold the TPU library, and every test worker imports this
file).  Mosaic refuses the 64-bit index maps that ``jax_enable_x64``
produces, and the chip entry points run in f32 without x64, so every
compile runs under ``jax.enable_x64(False)``.  The persistent compilation
cache is off around the compiles: an entry compiled for a described chip
cannot be read back without one.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.solver import SolverConfig
from repro.core.solver_fused import solve_fused_batched_qp
from repro.kernels import ops

L_EX = 51_200          # examples (ijcnn1-sized)
D = 22                 # features (ijcnn1), streamed as 24 feature rows
WIDTHS = (D, 780)      # ijcnn1's and mnist's feature counts
LANES = (8, 110, 512)  # one head, the LIBSVM-guide grid, a wide batch
PASS_A = ("plain", "masked", "doubled", "rows")
PASS_B = ("plain", "masked", "doubled", "conjugate", "rows")


def _at_widths(variants):
    """(variant, d) cases: the rbf variants at every width of the X
    stream, the Gram-bank rows variant (no X) once."""
    return [pytest.param(v, d, id=v if d == D else f"{v}-d{d}")
            for v in variants for d in WIDTHS if v != "rows" or d == D]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, *shapes):
    with jax.enable_x64(False):
        return jax.jit(fn).lower(*shapes).compile()


def _shapes(sharding, B, H, masked, conj, d=D):
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding)
    n = H * L_EX
    return dict(
        X=f32(L_EX, d), state=f32(B, n), lane=f32(B), rows=f32(B, L_EX),
        XQ=f32(B, d), idx=jax.ShapeDtypeStruct((B,), jnp.int32,
                                               sharding=sharding),
        flag=jax.ShapeDtypeStruct((B,), jnp.bool_, sharding=sharding),
        act=(jax.ShapeDtypeStruct((B, n), jnp.bool_, sharding=sharding)
             if masked else None),
        dirv=f32(B, n) if conj else None)


@pytest.mark.parametrize("B", LANES)
@pytest.mark.parametrize("variant,d", _at_widths(PASS_A))
def test_pass_a_compiles_for_v5e(one_chip, variant, B, d):
    dup = variant == "doubled"
    s = _shapes(one_chip, B, 2 if dup else 1, variant == "masked", False,
                d=d)
    st, ln = s["state"], s["lane"]
    if variant == "rows":
        def fn(KR, G, a, L, U, ai, Li, Ui, gi, i, ex):
            return ops.row_wss_batched_rows(KR, G, a, L, U, ai, Li, Ui, gi,
                                            i, ex, impl="pallas")
        args = (s["rows"], st, st, st, st, ln, ln, ln, ln, s["idx"],
                s["flag"])
    else:
        def fn(X, G, a, L, U, XQ, ai, Li, Ui, gi, i, ex, g, act=None):
            sqn = jnp.sum(X * X, axis=1)
            return ops.rbf_row_wss_batched(
                X, sqn, G, a, L, U, XQ, jnp.sum(XQ * XQ, axis=1), ai, Li,
                Ui, gi, i, ex, g, impl="pallas", dup=dup, act=act)
        args = (s["X"], st, st, st, st, s["XQ"], ln, ln, ln, ln, s["idx"],
                s["flag"], ln) + ((s["act"],) if s["act"] is not None
                                  else ())
    hlo = _compile(fn, *args).as_text()
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("B", LANES)
@pytest.mark.parametrize("variant,d", _at_widths(PASS_B))
def test_pass_b_compiles_for_v5e(one_chip, variant, B, d):
    dup = variant == "doubled"
    conj = variant == "conjugate"
    s = _shapes(one_chip, B, 2 if dup else 1, variant == "masked", conj,
                d=d)
    st, ln = s["state"], s["lane"]
    extra = tuple(v for v in (s["act"], s["dirv"]) if v is not None)

    def tail(rest):
        kw = {}
        if variant == "masked":
            kw["act"] = rest[0]
        if conj:
            kw["dirv"], kw["mu2"] = rest[0], rest[0][:, 0]
        return kw

    if variant == "rows":
        def fn(KRi, KRj, G, a, L, U, mu):
            return ops.update_wss_batched_rows(KRi, KRj, G, a, L, U, mu,
                                               impl="pallas")
        args = (s["rows"], s["rows"], st, st, st, st, ln)
    else:
        def fn(X, G, a, L, U, XQ, mu, g, *rest):
            sqn = jnp.sum(X * X, axis=1)
            sqq = jnp.sum(XQ * XQ, axis=1)
            return ops.rbf_update_wss_batched(
                X, sqn, G, a, L, U, XQ, sqq, XQ, sqq, mu, g, impl="pallas",
                dup=dup, **tail(rest))
        args = (s["X"], st, st, st, st, s["XQ"], ln, ln) + extra
    hlo = _compile(fn, *args).as_text()
    assert "tpu_custom_call" in hlo


def test_wide_feature_passes_compile_for_v5e(one_chip):
    # mnist width (d = 780, streamed as 784 feature rows): the X tile,
    # not the lane state, fills most of the step's VMEM
    s = _shapes(one_chip, 10, 1, False, False, d=780)
    st, ln = s["state"], s["lane"]

    def passes(X, G, a, L, U, XQ, v, i, ex, g):
        sqn = jnp.sum(X * X, axis=1)
        sqq = jnp.sum(XQ * XQ, axis=1)
        j, _ = ops.rbf_row_wss_batched(X, sqn, G, a, L, U, XQ, sqq, v, v, v,
                                       v, i, ex, g, impl="pallas")
        return j, ops.rbf_update_wss_batched(X, sqn, G, a, L, U, XQ, sqq, XQ,
                                             sqq, v, g, impl="pallas")

    hlo = _compile(passes, s["X"], st, st, st, st, s["XQ"], ln, s["idx"],
                   s["flag"], ln).as_text()
    assert hlo.count("tpu_custom_call") >= 2


def test_gram_kernel_compiles_for_v5e(one_chip):
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
    hlo = _compile(lambda A, B: ops.gram(A, B, 0.5, impl="pallas"),
                   f32(4096, D), f32(L_EX, D)).as_text()
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("B,mode", [
    (8, "plain"), (8, "shrinking"), (8, "conjugate"),
    (110, "plain"), (512, "plain"),
])
def test_fused_engine_compiles_for_v5e(one_chip, B, mode):
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
    cfg = SolverConfig(algorithm="smo" if mode == "conjugate" else "pasmo",
                       step="conjugate" if mode == "conjugate" else "plain")

    def fn(X, P, L, U, g):
        return solve_fused_batched_qp(X, P, L, U, g, cfg, impl="pallas",
                                      shrinking=mode == "shrinking")

    st = f32(B, L_EX)
    compiled = _compile(fn, f32(L_EX, D), st, st, st, f32(B))
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < 16e9, f"{used / 1e9:.1f} GB does not fit one v5e chip"


def test_plan_keeps_the_callers_block_and_splits_wide_batches():
    small = ops.pass_b_tiles(8, L_EX, D, 1024)
    assert (small.block_b, small.block_l) == (8, 1024)
    assert ops.pass_a_tiles(8, 100, D, 8).block_l == 8   # interpret sizes
    wide = ops.pass_b_tiles(4096, L_EX, D, 1024, H=2, masked=True,
                            conj=True)
    assert wide.block_l == 128 and wide.block_b < wide.bpad
    assert wide.bpad % wide.block_b == 0 and wide.bpad >= 4096
    for t, kw in ((small, {}), (wide, dict(H=2, masked=True, conj=True))):
        fp = dict(H=kw.get("H", 1), stacks=5 + kw.get("masked", False),
                  rows=2 * kw.get("conj", False), d=D)
        assert ops.vmem_bytes(t.block_b, t.block_l, **fp) <= ops.VMEM_BUDGET
    assert np.all([ops.pass_a_tiles(B, L_EX, D, 1024).block_l <= 1024
                   for B in LANES])
    # both benchmark cells keep the caller's block: ijcnn1 (one lane) and
    # mnist (ten lanes at d = 780, a (784, 1024) X tile)
    for B, l, d in ((1, 49_990, D), (10, 60_000, 780)):
        for t in (ops.pass_a_tiles(B, l, d, 1024),
                  ops.pass_b_tiles(B, l, d, 1024)):
            assert (t.block_l, t.lpad) == (1024, -(-l // 1024) * 1024)
