"""Pallas TPU kernel: dense tree-masked attention over staged draft tokens.

The intra-tree half of verification attention: T staged tokens attend over
each other under the ancestor-closure mask (dense (T, T) — MXU-friendly; see
DESIGN.md §3). The whole padded tree bucket lives in VMEM; one grid step per
(batch, kv-head). Returns partials (acc, m, l) merged with the flash-decode
cache partials in ops.py.

Layouts (rep = H // KV, R = rep * T rows, row = r * T + t):
  q:     (B, KV, R, hd)
  k/v:   (B, KV, T, hd)      staged draft keys/values
  mask:  (B, T, T) bool      ancestor-or-self & positional validity

Inside the kernel every block keeps the TPU tiling rule (the last two block
dims equal the array's or divide (8, 128)): the mask is pre-tiled over rep
to (B, R, T) int32 outside the kernel, so row r*T + t reads its own mask
row with no in-kernel gather, and m/l leave as (B, KV, R, 1) columns that
the wrapper squeezes back to (B, KV, R).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, mask_ref, acc_ref, m_ref, l_ref, *, scale):
    q = q_ref[0, 0].astype(jnp.float32) * scale       # (R, hd)
    k = k_ref[0, 0].astype(jnp.float32)               # (T, hd)
    v = v_ref[0, 0].astype(jnp.float32)
    vis = mask_ref[0] != 0                            # (R, T), tiled over rep

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )                                                 # (R, T)
    s = jnp.where(vis, s, NEG_INF)

    m = jnp.max(s, axis=-1, keepdims=True)            # (R, 1)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    acc_ref[0, 0] = o
    m_ref[0, 0] = m
    l_ref[0, 0] = l


def tree_attention_partial(
    q: jax.Array,        # (B, KV, R, hd)
    k_new: jax.Array,    # (B, KV, T, hd)
    v_new: jax.Array,
    mask: jax.Array,     # (B, T, T) bool
    *,
    interpret: bool = False,
    scale: float | None = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    B, KV, R, hd = q.shape
    T = k_new.shape[2]
    rep = R // T
    mask_rows = jnp.tile(mask.astype(jnp.int32), (1, rep, 1))   # (B, R, T)
    kernel = functools.partial(
        _kernel, scale=hd ** -0.5 if scale is None else scale
    )
    acc, m, l = pl.pallas_call(
        kernel,
        grid=(B, KV),
        in_specs=[
            pl.BlockSpec((1, 1, R, hd), lambda b, g: (b, g, 0, 0)),
            pl.BlockSpec((1, 1, T, hd), lambda b, g: (b, g, 0, 0)),
            pl.BlockSpec((1, 1, T, hd), lambda b, g: (b, g, 0, 0)),
            pl.BlockSpec((1, R, T), lambda b, g: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, R, hd), lambda b, g: (b, g, 0, 0)),
            pl.BlockSpec((1, 1, R, 1), lambda b, g: (b, g, 0, 0)),
            pl.BlockSpec((1, 1, R, 1), lambda b, g: (b, g, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, KV, R, hd), jnp.float32),
            jax.ShapeDtypeStruct((B, KV, R, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, KV, R, 1), jnp.float32),
        ],
        interpret=interpret,
        name="tree_attention_partial",
    )(q, k_new, v_new, mask_rows)
    return acc, m[..., 0], l[..., 0]
