"""Pallas kernels: symmetric per-tile int8 quantize/dequantize.

Beyond-paper optimization: silo models are int8-compressed before the
cross-silo exchange (IPFS put / pod-axis all-gather), cutting transfer bytes
4x (bf16) / 4x (f32->int8+scales). One VMEM pass each way.

Layout. A flat [N] vector is viewed as [N/128, 128]: on a TPU that reshape
is free, because a 128-lane row-major array is laid out linearly in HBM,
while a [N/1024, 1024] view would cost a relayout copy of the whole vector.
One quantization tile of 1024 elements is then 8 rows of 128 lanes, i.e.
one (8, 128) f32 vreg tile; a block of LANE tiles is (8 * LANE, 128) and is
viewed in VMEM as [LANE, 8, 128]. Scales travel as a [N/1024, 1] column,
whose block (LANE, 1) broadcasts over each tile's 8 x 128 elements.

The f32 side of a kernel may be the unpadded model vector of length n (a
multiple of 128): its last block then runs past the end, the quantizer
zeroes what it reads there, and writes past the end are dropped. The int8
side is always padded to a whole number of blocks (``padded``), so no
model-sized padded copy of the f32 vector is ever made.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TILE = 1024
LANE = 128            # quantization tiles per VMEM block
ROWS = TILE // 128    # 128-lane rows per quantization tile
BLOCK_ROWS = LANE * ROWS
BLOCK = TILE * LANE


def padded(n: int) -> int:
    """Length of the int8 form of an n-vector: whole blocks."""
    return -(-n // BLOCK) * BLOCK


def _q_kernel(x_ref, q_ref, s_ref, *, n: int):
    x = x_ref[...].astype(jnp.float32)
    if n % BLOCK:   # the last block reads past the vector's end: zero it
        row = (pl.program_id(0) * BLOCK_ROWS
               + jax.lax.broadcasted_iota(jnp.int32, x.shape, 0))
        lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
        x = jnp.where(row * 128 + lane < n, x, 0.0)
    x = x.reshape(LANE, ROWS, 128)
    amax = jnp.max(jnp.max(jnp.abs(x), axis=2, keepdims=True), axis=1,
                   keepdims=True)                              # [LANE, 1, 1]
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(x / scale), -127, 127)
    q_ref[...] = q.reshape(BLOCK_ROWS, 128).astype(jnp.int8)
    s_ref[...] = scale.reshape(LANE, 1)


def _dq_kernel(q_ref, s_ref, x_ref):
    q = q_ref[...].astype(jnp.float32).reshape(LANE, ROWS, 128)
    x = q * s_ref[...].reshape(LANE, 1, 1)
    x_ref[...] = x.reshape(x_ref.shape).astype(x_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def quantize(x, *, interpret: bool = False):
    """x: [n] (n % 128 == 0) -> (q int8 [Np], scales f32 [Np/TILE]),
    Np = padded(n); the padding quantizes as zeros."""
    n = x.shape[0]
    assert n % 128 == 0, "pad n to a multiple of 128"
    Np = padded(n)
    q, s = pl.pallas_call(
        functools.partial(_q_kernel, n=n),
        grid=(Np // BLOCK,),
        in_specs=[pl.BlockSpec((BLOCK_ROWS, 128), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((BLOCK_ROWS, 128), lambda i: (i, 0)),
                   pl.BlockSpec((LANE, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((Np // 128, 128), jnp.int8),
                   jax.ShapeDtypeStruct((Np // TILE, 1), jnp.float32)],
        name="quantize",
        interpret=interpret,
    )(x.reshape(n // 128, 128))
    return q.reshape(-1), s[:, 0]


@functools.partial(jax.jit, static_argnames=("dtype", "interpret"))
def dequantize_batch(q, scales, *, dtype=jnp.float32, interpret: bool = False):
    """Batched dequantize: K packed payloads in ONE kernel launch.

    q: [K, N] int8 (N % (TILE*LANE) == 0); scales: [K, N/TILE] -> [K, N].
    Same VMEM block body as ``dequantize`` with the payload index as the
    major grid axis — the scoring engine ingests a whole round's q8 models
    without K separate dispatches (oracle: ``ref.dequantize_rows``)."""
    K, N = q.shape
    assert N % (TILE * LANE) == 0, f"pad N to a multiple of {TILE * LANE}"
    rows = N // 128
    x = pl.pallas_call(
        _dq_kernel,
        grid=(K, rows // BLOCK_ROWS),
        in_specs=[pl.BlockSpec((None, BLOCK_ROWS, 128), lambda k, i: (k, i, 0)),
                  pl.BlockSpec((None, LANE, 1), lambda k, i: (k, i, 0))],
        out_specs=pl.BlockSpec((None, BLOCK_ROWS, 128), lambda k, i: (k, i, 0)),
        out_shape=jax.ShapeDtypeStruct((K, rows, 128), dtype),
        name="dequantize_batch",
        interpret=interpret,
    )(q.reshape(K, rows, 128), scales[:, :, None])
    return x.reshape(K, N)


@functools.partial(jax.jit, static_argnames=("n", "dtype", "interpret"))
def dequantize(q, scales, n: int, *, dtype=jnp.float32,
               interpret: bool = False):
    """q: [Np] int8 (whole blocks); scales: [Np/TILE] -> the first n
    elements (n % 128 == 0, n <= Np), written without a padded copy."""
    Np = q.shape[0]
    assert Np % BLOCK == 0 and n % 128 == 0 and n <= Np
    x = pl.pallas_call(
        _dq_kernel,
        grid=(pl.cdiv(n, BLOCK),),
        in_specs=[pl.BlockSpec((BLOCK_ROWS, 128), lambda i: (i, 0)),
                  pl.BlockSpec((LANE, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((BLOCK_ROWS, 128), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n // 128, 128), dtype),
        name="dequantize",
        interpret=interpret,
    )(q.reshape(Np // 128, 128), scales[:, None])
    return x.reshape(-1)
