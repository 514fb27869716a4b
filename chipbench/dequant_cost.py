"""Operations and bytes of one ``dequantize_batch`` call, from its shapes.

The kernel (``kernels/quant.dequantize_batch``) expands K int8 payloads in
the wire's layout (``q8ref``: one float32 scale per tile of 1024 codes) into
K rows of values, in one launch.
"""
from __future__ import annotations

from chipbench.q8ref import TILE


def dequantize_batch_cost(k: int, n: int, out_bytes: int):
    """K payloads of n elements into K x n values of ``out_bytes`` each, as
    the algorithm needs them: the K x n codes, the float32 scales of the
    ceil(n / 1024) tiles of each, the K x n values written; one multiply a
    code. Padding to whole blocks is the kernel's own cost, not counted."""
    bytes_ = k * n + k * -(-n // TILE) * 4 + k * n * out_bytes
    return bytes_, k * n
