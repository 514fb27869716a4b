"""Plain references of the int8 wire format and the costs of its kernels.

The format, as the wire's published layout states it: a flat vector is cut
into tiles of 1024 elements; each tile has the scale amax / 127 (1 where the
tile is all zeros) and its codes round(x / scale) clipped to [-127, 127];
the codes are padded with zeros to whole blocks of 131072. Everything here
is NumPy on the host, so it shares nothing with the kernels it checks.
"""
from __future__ import annotations

import numpy as np

TILE = 1024
BLOCK = 131072


def padded(n: int) -> int:
    return -(-n // BLOCK) * BLOCK


def rounded(a, dtype) -> np.ndarray:
    """``a`` rounded to ``dtype`` and held as float64: arithmetic in a lower
    precision, one rounding per operation (the control's precision)."""
    return np.asarray(a).astype(dtype).astype(np.float64)


def quantize(x: np.ndarray, dtype=np.float32):
    """[n] -> (q int8 [Np], scales f32 [Np / TILE]), computed in float32 (or,
    for the control, in a lower ``dtype``)."""
    n = x.shape[0]
    xp = np.zeros(padded(n), np.float32)
    xp[:n] = np.asarray(x, np.float32)
    t = rounded(xp.reshape(-1, TILE), dtype)
    amax = np.abs(t).max(axis=1, keepdims=True)
    scale = rounded(np.where(amax > 0, amax / 127.0, 1.0), dtype)
    q = np.clip(np.round(rounded(t / scale, dtype)), -127, 127)
    return q.astype(np.int8).reshape(-1), scale[:, 0].astype(np.float32)


def dequantize(q: np.ndarray, s: np.ndarray, n: int,
               dtype=np.float64) -> np.ndarray:
    """Codes times their tile's scale, as float64 rounded to ``dtype``."""
    t = rounded(q.reshape(-1, TILE).astype(np.float64)
                * rounded(s, dtype)[:, None], dtype)
    return t.reshape(-1)[:n]


# --------------------------------------------------------------------------- #
# Operations and bytes of one kernel call, from its shapes
# --------------------------------------------------------------------------- #

def wsum_q8_cost(m: int, n: int):
    """Weighted sum of m int8 payloads of n elements into an f32 [n], as the
    algorithm needs it: m x n codes, the f32 scales of the tiles that hold
    them, n f32 written; a multiply and an add per code (the scale-times-
    weight products per tile are negligible). Padding is the kernel's own
    cost, not work the sum needs, so it is not counted."""
    bytes_ = m * n + m * -(-n // TILE) * 4 + 4 * n
    return bytes_, 2 * m * n

