"""Jaxpr auditor: walk engine traces programmatically instead of
byte-diffing pretty-printed goldens.

Three audits over a matrix of engine configurations (:data:`MATRIX`):

* **structural equivalence** — for the feature-off configs (telemetry
  off, ``step="plain"``, shrinking mask off) the traced jaxpr's
  *structural signature* — the equation-primitive multiset plus the
  ``while_loop`` carry pytree structure (leaf shapes/dtypes) — must match
  the signature pinned in ``tests/golden/structural.json``.  A widened
  carry (a feature leaking state into the hot loop) or a changed
  primitive census fails the audit with a named diff instead of a
  1461-line golden byte-diff.  Carry structure is stable across jax
  versions and is always compared; the primitive multiset depends on jax
  lowering details, so it is compared strictly only when the running jax
  version matches the one recorded in the golden.

* **dtype audit** — every matrix entry is re-traced with *float32*
  inputs under ``jax_enable_x64``.  In that regime any ``float64``
  equation output is a weak-type promotion leak (an unadorned np scalar
  or dtype-less constructor) and any ``int64`` output is a leak out of
  the int32 index channel (PR 5's contract; exactness past l = 2^24 and
  on-device index width both depend on it).  ``convert_element_type``
  equations targeting f64/int64 are reported individually — they are the
  usual smoking gun.

* **host-callback scan** — no callback primitives
  (``pure_callback``/``io_callback``/``debug_callback``/debug prints)
  may appear inside a ``while_loop`` body: a callback in the hot loop
  syncs the host every iteration.

:func:`emit_census` writes the per-entry primitive/dtype census as JSON
artifacts (uploaded by the CI ``static-analysis`` job) so trace drift is
observable over time even when no invariant fires.
"""

from __future__ import annotations

import json
import os
from collections import Counter

from repro.analysis.report import Finding

# Small, fixed trace problem: big enough to exercise every code path
# (selection, planning history, doubled halves), small enough that every
# trace is milliseconds: l=16, d=4, B=3, C=2.0, seed 0.
AUDIT_L, AUDIT_D, AUDIT_B = 16, 4, 3

# Primitives that sync the host; forbidden inside while_loop bodies.
CALLBACK_PRIMS = ("pure_callback", "io_callback", "debug_callback",
                  "callback", "outside_call", "debug_print")


# ---------------------------------------------------------------------------
# jaxpr walking
# ---------------------------------------------------------------------------


def _sub_jaxprs(eqn):
    """Yield every (Closed)Jaxpr referenced by ``eqn``'s params.

    Covers pjit (``jaxpr``), while (``body_jaxpr``/``cond_jaxpr``), cond
    (``branches``), scan, custom_* wrappers and pallas_call — anything
    that stores a jaxpr or a list of them in its params.
    """
    for val in eqn.params.values():
        items = val if isinstance(val, (list, tuple)) else (val,)
        for item in items:
            if hasattr(item, "jaxpr") and hasattr(item.jaxpr, "eqns"):
                yield item.jaxpr          # ClosedJaxpr
            elif hasattr(item, "eqns"):
                yield item                # raw Jaxpr


def iter_eqns(jaxpr, path=()):
    """Depth-first (path, eqn) over ``jaxpr`` and every sub-jaxpr.

    ``path`` is the tuple of enclosing primitive names — e.g.
    ``("pjit", "while")`` for an equation inside the solve loop.
    """
    for eqn in jaxpr.eqns:
        yield path, eqn
        sub_path = path + (eqn.primitive.name,)
        for sub in _sub_jaxprs(eqn):
            yield from iter_eqns(sub, sub_path)


def _closed_inner(closed):
    return getattr(closed, "jaxpr", closed)


def primitive_census(closed) -> dict[str, int]:
    """Multiset of equation primitives over the whole trace."""
    c = Counter()
    for _, eqn in iter_eqns(_closed_inner(closed)):
        c[eqn.primitive.name] += 1
    return dict(sorted(c.items()))


def dtype_census(closed) -> dict[str, int]:
    """Multiset of equation-output dtypes over the whole trace."""
    c = Counter()
    for _, eqn in iter_eqns(_closed_inner(closed)):
        for v in eqn.outvars:
            aval = getattr(v, "aval", None)
            if aval is not None and hasattr(aval, "dtype"):
                c[str(aval.dtype)] += 1
    return dict(sorted(c.items()))


def while_carry_specs(closed) -> list[list[list]]:
    """Carry pytree structure of every ``while`` equation in the trace.

    Returns one entry per while_loop (document order): a list of
    ``[shape, dtype]`` pairs, one per carry leaf (the body jaxpr's
    non-constant invars).  This is the "did a feature widen the hot-loop
    carry" detector — it is independent of jaxpr pretty-printing and
    stable across jax versions.
    """
    out = []
    for _, eqn in iter_eqns(_closed_inner(closed)):
        if eqn.primitive.name != "while":
            continue
        body = eqn.params["body_jaxpr"].jaxpr
        nconsts = eqn.params["body_nconsts"]
        carry = body.invars[nconsts:]
        out.append([[list(v.aval.shape), str(v.aval.dtype)] for v in carry])
    return out


def signature(closed) -> dict:
    """Structural signature: primitive multiset + while-carry structure."""
    return {"primitives": primitive_census(closed),
            "carries": while_carry_specs(closed)}


# ---------------------------------------------------------------------------
# trace matrix
# ---------------------------------------------------------------------------


def _problem(dtype_name: str):
    import jax.numpy as jnp
    import numpy as np

    dtype = jnp.dtype(dtype_name)
    rng = np.random.default_rng(0)
    X = jnp.asarray(rng.normal(size=(AUDIT_L, AUDIT_D)), dtype)
    Y = jnp.asarray(np.sign(rng.normal(size=(AUDIT_B, AUDIT_L))), dtype)
    YC = Y * jnp.asarray(2.0, dtype)
    L, U = jnp.minimum(0.0, YC), jnp.maximum(0.0, YC)
    gam = jnp.asarray(rng.uniform(0.3, 1.0, AUDIT_B), dtype)
    return X, Y, L, U, gam


def _cfg(name: str):
    from repro.core.solver import SolverConfig

    return {
        "plain": lambda: SolverConfig(eps=1e-3, max_iter=500),
        "conjugate": lambda: SolverConfig(algorithm="smo", step="conjugate",
                                          eps=1e-3, max_iter=500),
        "pasmo": lambda: SolverConfig(algorithm="pasmo", eps=1e-3,
                                      max_iter=500),
    }[name]()


def _trace_fused(dtype_name, cfg_name, **kw):
    import jax

    from repro.core.solver_fused import solve_fused_batched_qp

    X, Y, L, U, gam = _problem(dtype_name)
    cfg = _cfg(cfg_name)
    return jax.make_jaxpr(
        lambda X, P, L, U, g: solve_fused_batched_qp(
            X, P, L, U, g, cfg, **kw))(X, Y, L, U, gam)


def _trace_fused_doubled(dtype_name, **kw):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import qp as qp_mod
    from repro.core.solver_fused import solve_fused_batched_qp

    X, _, _, _, gam = _problem(dtype_name)
    dtype = X.dtype
    rng = np.random.default_rng(1)
    y = jnp.asarray(rng.normal(size=(AUDIT_L,)), dtype)
    qp = qp_mod.svr_qp(y, 2.0, 0.1)
    P = jnp.broadcast_to(qp.p, (AUDIT_B, 2 * AUDIT_L))
    L = jnp.broadcast_to(qp.bounds.lower, (AUDIT_B, 2 * AUDIT_L))
    U = jnp.broadcast_to(qp.bounds.upper, (AUDIT_B, 2 * AUDIT_L))
    cfg = _cfg("plain")
    return jax.make_jaxpr(
        lambda X, P, L, U, g: solve_fused_batched_qp(
            X, P, L, U, g, cfg, doubled=True, **kw))(X, P, L, U, gam)


def _trace_fused_bank(dtype_name, **kw):
    import jax
    import jax.numpy as jnp

    from repro.core.solver_fused import solve_fused_batched_qp
    from repro.kernels import ops

    X, Y, L, U, gam = _problem(dtype_name)
    gram = ops.gram(X, X, gam[0])[None]
    gidx = jnp.zeros((AUDIT_B,), jnp.int32)
    cfg = _cfg("plain")
    return jax.make_jaxpr(
        lambda X, P, L, U, g, gram, gidx: solve_fused_batched_qp(
            X, P, L, U, g, cfg, gram=gram, gram_idx=gidx, **kw))(
        X, Y, L, U, gam, gram, gidx)


def _trace_classic(dtype_name, cfg_name):
    import jax

    from repro.core import qp as qp_mod
    from repro.core.solver import solve
    from repro.kernels import ops

    X, Y, _, _, gam = _problem(dtype_name)
    K = ops.gram(X, X, gam[0])
    y = Y[0]
    cfg = _cfg(cfg_name)
    return jax.make_jaxpr(
        lambda K, y: solve(qp_mod.PrecomputedKernel(K), y, 2.0, cfg))(K, y)


def _trace_sharded(dtype_name):
    import jax

    from repro.core.sharded_lanes import (resolve_lane_mesh,
                                          solve_fused_sharded_qp)

    X, Y, L, U, gam = _problem(dtype_name)
    mesh = resolve_lane_mesh(None, jax.devices()[:1])
    cfg = _cfg("plain")
    return jax.make_jaxpr(
        lambda X, P, L, U, g: solve_fused_sharded_qp(
            X, P, L, U, g, cfg, mesh=mesh, impl="jnp"))(X, Y, L, U, gam)


def _trace_telemetry(dtype_name, **kw):
    from repro.telemetry import RingConfig

    return _trace_fused(dtype_name, "plain",
                        telemetry=RingConfig(sample_every=8), **kw)


# name -> (tracer, pinned).  Pinned entries have their structural
# signature recorded in tests/golden/structural.json — they are the
# feature-off configurations whose trace must never drift when a new
# Python-gated feature lands (the byte-golden recipe, structurally).
MATRIX = {
    "plain_jnp": (lambda d: _trace_fused(d, "plain", impl="jnp"), True),
    "plain_shrink_jnp": (lambda d: _trace_fused(
        d, "plain", impl="jnp", shrinking=True), True),
    "plain_interpret": (lambda d: _trace_fused(
        d, "plain", impl="interpret", block_l=8), True),
    "conjugate_jnp": (lambda d: _trace_fused(
        d, "conjugate", impl="jnp"), True),
    "conjugate_interpret": (lambda d: _trace_fused(
        d, "conjugate", impl="interpret", block_l=8), True),
    "pasmo_jnp": (lambda d: _trace_fused(d, "pasmo", impl="jnp"), False),
    "telemetry_jnp": (lambda d: _trace_telemetry(d, impl="jnp"), False),
    "doubled_jnp": (lambda d: _trace_fused_doubled(d, impl="jnp"), False),
    "doubled_interpret": (lambda d: _trace_fused_doubled(
        d, impl="interpret", block_l=8), False),
    "bank_jnp": (lambda d: _trace_fused_bank(d, impl="jnp"), False),
    "classic_smo": (lambda d: _trace_classic(d, "plain"), False),
    "classic_pasmo": (lambda d: _trace_classic(d, "pasmo"), False),
    "sharded_plain": (lambda d: _trace_sharded(d), False),
}

PINNED = tuple(k for k, (_, pinned) in MATRIX.items() if pinned)


def trace_entry(name: str, dtype_name: str = "float64"):
    tracer, _ = MATRIX[name]
    return tracer(dtype_name)


# ---------------------------------------------------------------------------
# audits
# ---------------------------------------------------------------------------


def audit_dtypes(closed, entry: str,
                 expect_float: str = "float32") -> list[Finding]:
    """Flag f64 weak-type promotion and int64 index leaks in one trace.

    The trace must have been built from ``expect_float`` inputs with
    ``jax_enable_x64`` on — then every float64 output is a promotion the
    input dtype did not ask for, and every int64 output left the int32
    index channel.
    """
    findings = []
    assert expect_float == "float32", "the probe traces f32 inputs"
    for path, eqn in iter_eqns(_closed_inner(closed)):
        loc = "/".join(path) or "<top>"
        for v in eqn.outvars:
            aval = getattr(v, "aval", None)
            if aval is None or not hasattr(aval, "dtype"):
                continue
            dt = str(aval.dtype)
            if dt == "float64":
                findings.append(Finding(
                    "dtype-f64", entry,
                    f"{eqn.primitive.name} at {loc} produces float64 from "
                    f"float32 inputs (weak-type promotion leak)"))
            elif dt == "int64":
                findings.append(Finding(
                    "dtype-int64", entry,
                    f"{eqn.primitive.name} at {loc} produces int64 "
                    f"(index left the int32 channel)"))
        if eqn.primitive.name == "convert_element_type":
            new = str(eqn.params.get("new_dtype", ""))
            if new in ("float64", "int64"):
                findings.append(Finding(
                    "dtype-convert", entry,
                    f"convert_element_type -> {new} at {loc}"))
    return findings


def audit_callbacks(closed, entry: str) -> list[Finding]:
    """No host-callback primitives inside while_loop bodies."""
    findings = []
    for path, eqn in iter_eqns(_closed_inner(closed)):
        name = eqn.primitive.name
        if "while" not in path:
            continue
        if name in CALLBACK_PRIMS or "callback" in name:
            findings.append(Finding(
                "host-callback", entry,
                f"{name} inside while_loop body at {'/'.join(path)} — "
                f"host sync every iteration"))
    return findings


def compare_signature(got: dict, want: dict, entry: str,
                      strict_primitives: bool = True) -> list[Finding]:
    """Structural diff of two signatures, rendered as findings."""
    findings = []
    gc, wc = got["carries"], want["carries"]
    if len(gc) != len(wc):
        findings.append(Finding(
            "struct-carry", entry,
            f"{len(gc)} while_loop(s) traced, golden has {len(wc)}"))
    else:
        for k, (g, w) in enumerate(zip(gc, wc)):
            if g == w:
                continue
            if len(g) != len(w):
                findings.append(Finding(
                    "struct-carry", entry,
                    f"while_loop #{k} carry widened: {len(g)} leaves vs "
                    f"{len(w)} in the golden (a feature leaked state "
                    f"into the feature-off hot loop)"))
            else:
                diffs = [f"leaf {n}: {tuple(a[0])}/{a[1]} vs "
                         f"{tuple(b[0])}/{b[1]}"
                         for n, (a, b) in enumerate(zip(g, w)) if a != b]
                findings.append(Finding(
                    "struct-carry", entry,
                    f"while_loop #{k} carry leaf specs drifted: "
                    + "; ".join(diffs[:4])))
    if strict_primitives and got["primitives"] != want["primitives"]:
        gp, wp = got["primitives"], want["primitives"]
        delta = []
        for prim in sorted(set(gp) | set(wp)):
            a, b = gp.get(prim, 0), wp.get(prim, 0)
            if a != b:
                delta.append(f"{prim}: {b} -> {a}")
        findings.append(Finding(
            "struct-prims", entry,
            "primitive census drifted vs golden: " + ", ".join(delta)))
    return findings


def default_golden_path(root: str | None = None) -> str:
    root = root or repo_root()
    return os.path.join(root, "tests", "golden", "structural.json")


def repo_root() -> str:
    """Best-effort repo root: the checkout this package was imported
    from, else the current directory (installed-package fallback)."""
    here = os.path.dirname(os.path.abspath(__file__))
    cand = os.path.abspath(os.path.join(here, "..", "..", ".."))
    for marker in ("pyproject.toml", "pytest.ini"):
        if os.path.exists(os.path.join(cand, marker)):
            return cand
    return os.getcwd()


def emit_golden(path: str) -> None:
    """(Re)write the pinned structural signatures.

    Run after an INTENTIONAL trace change to the feature-off engine, and
    review the JSON diff.
    """
    import jax

    assert jax.config.jax_enable_x64, "capture requires jax_enable_x64"
    entries = {name: signature(trace_entry(name)) for name in PINNED}
    payload = {"jax": jax.__version__, "entries": entries}
    with open(path, "w") as fh:
        json.dump(payload, fh, indent=1, sort_keys=True)
        fh.write("\n")


def audit_structure(golden_path: str | None = None) -> list[Finding]:
    """Feature-off structural equivalence vs the pinned golden.

    Also re-traces the plain config after tracing the feature-on configs
    and asserts the signature is unchanged (no tracing-cache bleed) and
    that the telemetry ring really widens the carry when ON (the audit
    itself would be vacuous if both traces looked alike).
    """
    import jax

    golden_path = golden_path or default_golden_path()
    if not os.path.exists(golden_path):
        return [Finding(
            "struct-golden", golden_path,
            "structural golden missing — regenerate with "
            "`python -m repro.analysis --emit-golden`")]
    with open(golden_path) as fh:
        golden = json.load(fh)
    strict = golden.get("jax") == jax.__version__
    if not strict:
        print(f"jaxpr_audit: golden captured on jax {golden.get('jax')}, "
              f"running {jax.__version__} — primitive census compared "
              f"report-only, carry structure still strict")
    findings = []
    sigs = {}
    for name in PINNED:
        want = golden["entries"].get(name)
        if want is None:
            findings.append(Finding(
                "struct-golden", name,
                "pinned entry missing from the structural golden — "
                "regenerate it"))
            continue
        sigs[name] = signature(trace_entry(name))
        findings.extend(compare_signature(
            sigs[name], want, name, strict_primitives=strict))

    # feature-on sanity: the ring must widen the carry (otherwise the
    # equivalence audit above proves nothing) ...
    on = signature(trace_entry("telemetry_jnp"))
    base = sigs.get("plain_jnp")
    if base is not None:
        if on["carries"] == base["carries"]:
            findings.append(Finding(
                "struct-feature", "telemetry_jnp",
                "telemetry=RingConfig() did not widen the while carry — "
                "the ring is not riding the loop"))
        # ... and re-tracing plain afterwards must reproduce the same
        # structure (no tracing-cache bleed between configs).
        again = signature(trace_entry("plain_jnp"))
        if again != base:
            findings.append(Finding(
                "struct-invariance", "plain_jnp",
                "plain signature changed after tracing feature-on "
                "configs in-process"))
    return findings


def audit_all_dtypes(names=None) -> list[Finding]:
    """Dtype + callback audit across the matrix (f32 probe inputs)."""
    findings = []
    for name in names or MATRIX:
        closed = trace_entry(name, "float32")
        findings.extend(audit_dtypes(closed, name))
        findings.extend(audit_callbacks(closed, name))
    return findings


def emit_census(out_dir: str, names=None, dtype_name: str = "float64"):
    """Write one census JSON per matrix entry; returns the paths."""
    import jax

    os.makedirs(out_dir, exist_ok=True)
    paths = []
    for name in names or MATRIX:
        closed = trace_entry(name, dtype_name)
        payload = {
            "entry": name,
            "jax": jax.__version__,
            "input_dtype": dtype_name,
            "primitives": primitive_census(closed),
            "dtypes": dtype_census(closed),
            "carries": while_carry_specs(closed),
        }
        path = os.path.join(out_dir, f"census_{name}.json")
        with open(path, "w") as fh:
            json.dump(payload, fh, indent=1, sort_keys=True)
            fh.write("\n")
        paths.append(path)
    return paths


# ---------------------------------------------------------------------------
# planted violations (negative controls for the CLI / tests)
# ---------------------------------------------------------------------------


def plant_f64() -> list:
    """Trace the plain engine with a deliberate f64 round-trip on the
    linear term; the dtype audit MUST flag it (requires x64 enabled,
    otherwise the planted cast is a no-op)."""
    import jax
    import jax.numpy as jnp

    from repro.core.solver_fused import solve_fused_batched_qp

    assert jax.config.jax_enable_x64, "plant_f64 needs JAX_ENABLE_X64"
    X, Y, L, U, gam = _problem("float32")
    cfg = _cfg("plain")
    closed = jax.make_jaxpr(
        lambda X, P, L, U, g: solve_fused_batched_qp(
            X, P.astype(jnp.float64).astype(P.dtype), L, U, g, cfg,
            impl="jnp"))(X, Y, L, U, gam)
    return audit_dtypes(closed, "plant:f64")


def plant_widened_carry() -> list:
    """Compare the telemetry-ON trace against the plain signature: the
    ring widens the while carry, so the structural check MUST flag it."""
    got = signature(trace_entry("telemetry_jnp"))
    want = signature(trace_entry("plain_jnp"))
    return compare_signature(got, want, "plant:carry",
                             strict_primitives=False)


def assert_structural(name: str, golden_path: str | None = None) -> None:
    """pytest helper: assert matrix entry ``name`` matches the structural
    golden (carry pytree always; primitive multiset only on the pinned
    jax version, mirroring the retired byte-golden skip)."""
    import jax

    with open(golden_path or default_golden_path()) as fh:
        golden = json.load(fh)
    strict = golden["jax"] == jax.__version__
    got = signature(trace_entry(name))
    finds = compare_signature(got, golden["entries"][name], name,
                              strict_primitives=strict)
    assert not finds, "\n".join(f.render() for f in finds)
