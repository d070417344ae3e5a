"""Pallas TPU kernel: W8A8 dynamic-quantized matmul (ActivationQuant DSIA).

QSpec-style quantized drafting: activations are per-row symmetric int8,
weights per-column int8; the MXU runs the int8 x int8 -> int32 dot and the
epilogue rescales. Tiled (bm, bn, bk) with an f32 VMEM accumulator carried
over the K grid dimension.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, w_ref, xs_ref, ws_ref, o_ref, acc_scr, *, nk: int):
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    x = x_ref[...]                                     # (bm, bk) int8
    w = w_ref[...]                                     # (bk, bn) int8
    acc_scr[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32
    ).astype(jnp.float32)

    @pl.when(kk == nk - 1)
    def _fini():
        xs = xs_ref[...]                               # (bm, 1) f32
        ws = ws_ref[...]                               # (1, bn) f32
        o_ref[...] = acc_scr[...] * xs * ws


def quantize_rows(
    x: jax.Array, k_axis: str | None = None
) -> Tuple[jax.Array, jax.Array]:
    """Per-row symmetric int8: returns (x_int8 (M,K), scale (M,1) f32).

    ``k_axis`` names the mesh axis K is split over (inside ``shard_map``):
    the row maximum is then taken across it, so every shard quantizes on
    the unsplit row's grid."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    if k_axis is not None:
        amax = jax.lax.pmax(amax, k_axis)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127).astype(jnp.int8)
    return q, scale


def quantize_cols(
    w: jax.Array, k_axis: str | None = None
) -> Tuple[jax.Array, jax.Array]:
    """Per-column symmetric int8: returns (w_int8 (K,N), scale (1,N) f32);
    ``k_axis`` as in ``quantize_rows``."""
    amax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=0, keepdims=True)
    if k_axis is not None:
        amax = jax.lax.pmax(amax, k_axis)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(w.astype(jnp.float32) / scale), -127, 127).astype(jnp.int8)
    return q, scale


def int8_matmul(
    x_q: jax.Array,      # (M, K) int8
    w_q: jax.Array,      # (K, N) int8
    x_scale: jax.Array,  # (M, 1) f32
    w_scale: jax.Array,  # (1, N) f32
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    M, K = x_q.shape
    N = w_q.shape[1]
    bm, bn, bk = min(block_m, M), min(block_n, N), min(block_k, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, "pad in ops.py"
    nk = K // bk
    kernel = functools.partial(_kernel, nk=nk)
    return pl.pallas_call(
        kernel,
        grid=(M // bm, N // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bm, 1), lambda i, j, kk: (i, 0)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        name="int8_matmul",
    )(x_q, w_q, x_scale, w_scale)
