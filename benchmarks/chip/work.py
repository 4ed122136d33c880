"""Work model of the two Pallas passes, and the chip peaks it is held to.

Operations and bytes are counted from the problem's own sizes — ``l``
examples, ``d`` features, ``B`` lanes, ``H`` variable halves per lane
(2 for the doubled epsilon-SVR operator) — never from the padded shapes
the kernels run at, so padding shows as lost roofline share and not as
work:

=======  ==========  ==========================================
pass     FLOPs       bytes
=======  ==========  ==========================================
A        2 B l d     4 l d (X) + 5 B H l (G in f32, a status byte)
B        4 B l d     4 l d (X) + 9 B H l (G read and written, status)
=======  ==========  ==========================================

Pass A computes one kernel row per lane (a (B, d) x (d, l) product) and
scans G; pass B computes two rows per lane and updates G.  The kernel
rows are over the base ``l`` examples whatever ``H`` is.

The least time of a launch is the larger of FLOPs over the bf16 peak and
bytes over the HBM bandwidth.  Both are lower bounds: an f32 matmul at
``HIGHEST`` precision takes several bf16 passes, and the byte side
counts X once per launch, which holds only where X cannot stay on chip
between launches (:func:`x_streams`).
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclass(frozen=True)
class Work:
    flops: float
    bytes: float


def pass_work(l: int, d: int, B: int, H: int = 1) -> dict[str, Work]:
    """FLOPs and HBM bytes of one launch of each pass."""
    x_bytes = 4.0 * l * d
    return {"pass_a": Work(2.0 * B * l * d, x_bytes + 5.0 * B * H * l),
            "pass_b": Work(4.0 * B * l * d, x_bytes + 9.0 * B * H * l)}


def peaks(device_kind: str) -> dict:
    """Peak table entry of ``device_kind``; an unknown device raises."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def least_time(w: Work, device_kind: str) -> tuple[float, str]:
    """Least seconds a launch can take on ``device_kind``, and which side
    bounds it (``"flops"`` or ``"hbm"``)."""
    p = peaks(device_kind)
    t_f = w.flops / p["flops_bf16"]
    t_b = w.bytes / p["hbm_bytes_per_s"]
    return (t_f, "flops") if t_f >= t_b else (t_b, "hbm")


def x_streams(l: int, d: int, device_kind: str) -> bool:
    """True where X (f32) is larger than the chip's on-chip memory, so
    every launch has to read it from HBM and the byte bound holds."""
    return 4.0 * l * d > peaks(device_kind)["on_chip_bytes"]
