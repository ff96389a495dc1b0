"""The controls' lower precision: bfloat16 rounding in numpy."""
from __future__ import annotations

import numpy as np


def bf16(x) -> np.ndarray:
    """Round to bfloat16 (nearest, ties to even); float32 out."""
    b = np.asarray(x, np.float32).view(np.uint32)
    b = (b + np.uint32(0x7FFF) + ((b >> np.uint32(16)) & np.uint32(1))) \
        & np.uint32(0xFFFF0000)
    return b.view(np.float32)
