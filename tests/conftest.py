import os
import re
import subprocess
import sys

import jax

# The QP solver tests need f64 (chess-board uses C=1e6).  Model smoke tests
# use explicit f32/bf16 dtypes, so the flag is harmless there.  The dry-run
# device-count flag is intentionally NOT set here (smoke tests see 1 device).
jax.config.update("jax_enable_x64", True)

# Kernel-backend toggle for the fused-engine tests (the nightly CI interpret
# leg): REPRO_IMPL=interpret re-runs them through the batched Pallas kernels
# in interpret mode instead of the jnp oracle (REPRO_BLOCK_L tunes the block
# size; small keeps interpret-mode padding cheap).  Default stays jnp — the
# tier-1 fast path.  Tests import FUSED_KW and splat it into fused-engine
# calls.
FUSED_IMPL = os.environ.get("REPRO_IMPL", "jnp")
FUSED_KW = {"impl": FUSED_IMPL}
if FUSED_IMPL != "jnp":
    FUSED_KW["block_l"] = int(os.environ.get("REPRO_BLOCK_L", "128"))


def run_multidevice(script: str, n_devices: int = 8, *,
                    timeout: int = 600) -> str:
    """Run ``script`` in a fresh interpreter with ``n_devices`` forced host
    CPU devices; return its stdout.

    ``--xla_force_host_platform_device_count`` must be set before jax is
    imported, and the running suite must keep seeing a single device (the
    dry-run rule), so multi-device tests respawn: the flag is composed into
    ``XLA_FLAGS`` (replacing any inherited device-count flag), ``PYTHONPATH``
    gains ``src/``, and the child owns imports and x64 config itself.  A
    non-zero exit asserts with the stderr tail.  Mark callers
    ``@pytest.mark.slow`` — each respawn pays a fresh jit warm-up.
    """
    env = dict(os.environ)
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   env.get("XLA_FLAGS", "")).strip()
    env["XLA_FLAGS"] = (f"{flags} " if flags else "") + \
        f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = os.pathsep.join(p for p in (
        os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src")),
        env.get("PYTHONPATH")) if p)
    proc = subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True, env=env,
                          timeout=timeout)
    assert proc.returncode == 0, \
        f"multi-device subprocess failed:\n{proc.stderr[-4000:]}"
    return proc.stdout
