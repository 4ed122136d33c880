"""Shared set-up of the chip benchmark's own tests.

They run on the CPU (``JAX_PLATFORMS=cpu``), except ``test_control.py``,
which needs a TPU: ``python -m pytest benchmarks/chip/tests``.  The
harness's modules are imported the way ``run.py`` imports them, from
``benchmarks/chip`` itself, and the program from ``src``.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(CHIP))
for p in (CHIP, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
