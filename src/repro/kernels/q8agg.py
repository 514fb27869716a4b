"""Pallas kernels: int8-native fused aggregation (quantized exchange hot path).

The cross-silo round moves M peer models of flattened length N as int8
payloads (symmetric per-tile quantization, ``kernels/quant.py``). The seed
pipeline dequantized them to f32 and only then ran the weighted-sum /
MultiKRUM-Gram kernels — one extra f32 materialization of the whole [M, N]
set, 4x the HBM traffic of the int8 bytes that actually arrived.

These kernels consume the packed int8 blocks plus their per-tile scales
directly, fusing dequantization into the accumulation:

  wsum_q8:  out[n]  = sum_m w[m] * s[m, n // QT] * q[m, n]
            The weight folds into the per-tile scale, so each model's int8
            block is scaled and accumulated in one VMEM pass.
  gram_q8:  G[i, j] = sum_n (s q)[i, n] * (s q)[j, n]
            Per quant tile, the int8 dot product q_i . q_j is exact
            (|sum| <= 127^2 * QT < 2^24); scales apply once per tile as
            s_i * s_j.

HBM traffic per round drops from (1 + 4 + 4) * M * N bytes (read int8, write
f32, re-read f32) to ~1.004 * M * N (int8 + scales), one pass.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import quant as _q

QT = _q.TILE          # quantization tile (scale granularity), 1024
QPB = _q.LANE         # quant tiles per VMEM block
TILE_N = _q.BLOCK     # kernel block width along N (the wire's 131072 padding)
ROWS = _q.ROWS        # 128-lane rows per quant tile
BLOCK_ROWS = _q.BLOCK_ROWS

# Layout (see ``kernels/quant.py``): each flat payload is viewed as
# [N/128, 128], free on a TPU, so one quant tile is 8 rows of 128 lanes and
# a block of QPB tiles is (QPB * 8, 128), viewed in VMEM as [QPB, 8, 128].
# Scales travel as [.., N/QT, 1] columns whose (QPB, 1) block broadcasts
# over each tile. A stack of M payloads is [M, N/128, 128].


def _tiles(ref, m=None):
    """Block (or model m's block) of int8 payload as f32 [QPB, 8, 128]."""
    blk = ref[...] if m is None else ref[m]
    return blk.astype(jnp.float32).reshape(QPB, ROWS, 128)


def _wsum_kernel(ws_ref, q_ref, o_ref):
    """ws_ref: [M, QPB, 1] f32 (w[m] * s[m, t]); q_ref: [M, QPB*8, 128] int8;
    o_ref: [QPB*8, 128] f32."""
    acc = jnp.zeros((QPB, ROWS, 128), jnp.float32)
    for m in range(q_ref.shape[0]):
        acc = acc + _tiles(q_ref, m) * ws_ref[m].reshape(QPB, 1, 1)
    o_ref[...] = acc.reshape(BLOCK_ROWS, 128)


@functools.partial(jax.jit, static_argnames=("n", "interpret"))
def wsum_q8(q, scales, w, n: int, *, interpret: bool = False):
    """q: [M, N] int8 (N % TILE_N == 0); scales: [M, N/QT]; w: [M]
    -> the first n elements (n % 128 == 0, n <= N) of the f32 sum."""
    M, N = q.shape
    assert N % TILE_N == 0, f"pad N to a multiple of {TILE_N}"
    assert scales.shape == (M, N // QT), scales.shape
    assert n % 128 == 0 and n <= N
    ws = w.astype(jnp.float32)[:, None] * scales          # weight folds into s
    out = pl.pallas_call(
        _wsum_kernel,
        grid=(pl.cdiv(n, TILE_N),),
        in_specs=[pl.BlockSpec((M, QPB, 1), lambda i: (0, i, 0)),
                  pl.BlockSpec((M, BLOCK_ROWS, 128), lambda i: (0, i, 0))],
        out_specs=pl.BlockSpec((BLOCK_ROWS, 128), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n // 128, 128), jnp.float32),
        name="wsum_q8",
        interpret=interpret,
    )(ws[:, :, None], q.reshape(M, N // 128, 128))
    return out.reshape(n)


def _add_delta_kernel(b_ref, q_ref, s_ref, o_ref):
    """b_ref/o_ref: [QPB*8, 128] f32; q_ref: [QPB*8, 128] int8; s_ref:
    [QPB, 1]. Dequantization fuses into the add: the f32 delta never hits
    HBM."""
    d = _tiles(q_ref) * s_ref[...].reshape(QPB, 1, 1)
    o_ref[...] = b_ref[...] + d.reshape(BLOCK_ROWS, 128)


@functools.partial(jax.jit, static_argnames=("interpret",))
def add_q8_delta(base, q, scales, *, interpret: bool = False):
    """base: [n] f32 (n % 128 == 0); q: [N] int8 delta (N % TILE_N == 0,
    n <= N); scales: [N/QT] f32 -> [n] f32 = base + dequantized delta, one
    pass, with no padded copy of base."""
    n, N = base.shape[0], q.shape[0]
    assert N % TILE_N == 0, f"pad N to a multiple of {TILE_N}"
    assert n % 128 == 0 and n <= N and scales.shape == (N // QT,)
    blk = pl.BlockSpec((BLOCK_ROWS, 128), lambda i: (i, 0))
    out = pl.pallas_call(
        _add_delta_kernel,
        grid=(pl.cdiv(n, TILE_N),),
        in_specs=[blk, blk, pl.BlockSpec((QPB, 1), lambda i: (i, 0))],
        out_specs=blk,
        out_shape=jax.ShapeDtypeStruct((n // 128, 128), jnp.float32),
        name="add_q8_delta",
        interpret=interpret,
    )(base.astype(jnp.float32).reshape(n // 128, 128),
      q.reshape(N // 128, 128), scales[:, None])
    return out.reshape(-1)


def _gram_kernel(s_ref, q_ref, g_ref):
    """s_ref: [M, QPB, 1] f32; q_ref: [M, QPB*8, 128] int8; accumulates
    G [M, M]."""
    @pl.when(pl.program_id(0) == 0)
    def _init():
        g_ref[...] = jnp.zeros_like(g_ref)

    M = q_ref.shape[0]
    dots = {}
    for a in range(M):
        qa = _tiles(q_ref, a)
        sa = s_ref[a].reshape(QPB, 1, 1)
        for b in range(a, M):
            # per-tile int8 dot product: every partial sum is an integer of
            # magnitude <= 127^2 * QT < 2^24, so the f32 sum is exact
            d = jnp.sum(jnp.sum(qa * _tiles(q_ref, b), axis=2, keepdims=True),
                        axis=1, keepdims=True)                # [QPB, 1, 1]
            dots[a, b] = dots[b, a] = jnp.sum(
                d * sa * s_ref[b].reshape(QPB, 1, 1), axis=0)  # [1, 1]
    # assemble [M, M] with one-axis broadcasts only (Mosaic has no
    # sublane-and-lane broadcast of a scalar)
    col = jax.lax.broadcasted_iota(jnp.int32, (1, M), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (M, M), 0)
    g = jnp.zeros((M, M), jnp.float32)
    for a in range(M):
        r = jnp.zeros((1, M), jnp.float32)
        for b in range(M):
            r = r + jnp.where(col == b, jnp.broadcast_to(dots[a, b], (1, M)),
                              0.0)
        g = g + jnp.where(row == a, jnp.broadcast_to(r, (M, M)), 0.0)
    g_ref[...] += g


@functools.partial(jax.jit, static_argnames=("interpret",))
def gram_q8(q, scales, *, interpret: bool = False):
    """q: [M, N] int8 (N % TILE_N == 0); scales: [M, N/QT]
    -> (G [M, M] f32, sq [M, 1] f32) of the dequantized models."""
    M, N = q.shape
    assert N % TILE_N == 0, f"pad N to a multiple of {TILE_N}"
    assert scales.shape == (M, N // QT), scales.shape
    rows = N // 128
    g = pl.pallas_call(
        _gram_kernel,
        grid=(rows // BLOCK_ROWS,),
        in_specs=[pl.BlockSpec((M, QPB, 1), lambda i: (0, i, 0)),
                  pl.BlockSpec((M, BLOCK_ROWS, 128), lambda i: (0, i, 0))],
        out_specs=pl.BlockSpec((M, M), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((M, M), jnp.float32),
        name="gram_q8",
        interpret=interpret,
    )(scales.astype(jnp.float32)[:, :, None], q.reshape(M, rows, 128))
    return g, jnp.diagonal(g)[:, None]
