"""Pallas TPU kernel: flash-decode attention over a committed KV cache.

The memory-bound hot loop of speculative *verification*: T staged query rows
(tree bucket, or T=1 for plain AR decode) attend over an S-long KV cache.
KV is streamed HBM->VMEM in ``block_s`` chunks along the innermost grid dim
with online-softmax scratch carried in VMEM across chunks; the (small) query
block stays resident in VMEM. Returns un-normalized partials (acc, m, l) so
the caller can merge with the staged-token tree attention (see ops.py) —
exactly the flash-decoding split-KV combine, adapted to the verify step.

Layouts (per kv-head group g, GQA rep = H // KV):
  q:      (B, KV, R, hd)   R = rep * T query rows, hd padded to 128
  k/v:    (B, KV, S, hd)   S padded to block_s
  kv_pos: (B, S) int32     slot position, -1 = invalid (ring/empty)
  q_pos:  (B, R) int32     absolute position per query row
Outputs: acc (B, KV, R, hd) f32, m/l (B, KV, R) f32.

Every block keeps the TPU tiling rule (the last two block dims equal the
array's or divide (8, 128)): the wrappers hand the kernel kv_pos as
(B, nk, 1, blk) rows, q_pos as a (B, R, 1) column and take m/l back as
(B, KV, R, 1) columns, squeezing both ends back to the layouts above.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(
    q_ref, k_ref, v_ref, kvpos_ref, qpos_ref,          # inputs
    acc_ref, m_ref, l_ref,                             # outputs
    m_scr, l_scr, o_scr,                               # VMEM scratch
    *, kind: str, window: int, sink: int, scale: float, nk: int,
):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        o_scr[...] = jnp.zeros_like(o_scr)

    q = q_ref[0, 0].astype(jnp.float32) * scale        # (R, hd)
    k = k_ref[0, 0].astype(jnp.float32)                # (blk, hd)
    v = v_ref[0, 0].astype(jnp.float32)
    kpc = kvpos_ref[0, 0]                              # (1, blk)
    qpc = qpos_ref[0]                                  # (R, 1)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )                                                  # (R, blk)
    valid = (kpc >= 0) & (kpc <= qpc)
    if kind == "window":
        valid &= kpc > qpc - window
    elif kind == "streaming":
        valid &= (kpc < sink) | (kpc > qpc - window)
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_scr[...]                                # (R, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)                             # (R, blk)
    corr = jnp.exp(m_prev - m_new)                     # (R, 1)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    o_scr[...] = o_scr[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_scr[...] = m_new

    @pl.when(j == nk - 1)
    def _fini():
        acc_ref[0, 0] = o_scr[...]
        m_ref[0, 0] = m_scr[...]
        l_ref[0, 0] = l_scr[...]


def _out_shapes(B: int, KV: int, R: int, hd: int) -> list:
    """acc (B, KV, R, hd) and the m/l columns (B, KV, R, 1), all f32."""
    return [
        jax.ShapeDtypeStruct((B, KV, R, hd), jnp.float32),
        jax.ShapeDtypeStruct((B, KV, R, 1), jnp.float32),
        jax.ShapeDtypeStruct((B, KV, R, 1), jnp.float32),
    ]


def _paged_kernel(
    tbl_ref,                                           # scalar prefetch (B, n_pp)
    q_ref, k_ref, v_ref, kvpos_ref, qpos_ref,          # inputs
    acc_ref, m_ref, l_ref,                             # outputs
    m_scr, l_scr, o_scr,                               # VMEM scratch
    *, kind: str, window: int, sink: int, scale: float, nk: int,
):
    # identical math to _kernel — only the k/v BlockSpec index_maps differ
    # (they dereference the prefetched page table), so the masking contract
    # is shared verbatim
    del tbl_ref
    _kernel(
        q_ref, k_ref, v_ref, kvpos_ref, qpos_ref,
        acc_ref, m_ref, l_ref, m_scr, l_scr, o_scr,
        kind=kind, window=window, sink=sink, scale=scale, nk=nk,
    )


def flash_decode_paged_partial(
    q: jax.Array,           # (B, KV, R, hd)
    k_pages: jax.Array,     # (NP, KV, P, hd) shared page pool
    v_pages: jax.Array,
    page_table: jax.Array,  # (B, n_pp) int32, -1 = unallocated
    kv_pos: jax.Array,      # (B, n_pp * P) int32, -1 = invalid
    q_pos: jax.Array,       # (B, R) int32
    *,
    kind: str = "causal",
    window: int = 0,
    sink: int = 0,
    interpret: bool = False,
    scale: float | None = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Block-paged flash-decode partials: the page table rides as a SCALAR
    PREFETCH operand and the k/v BlockSpec index_maps dereference it, so the
    j-th KV chunk streamed HBM->VMEM is pool page ``page_table[b, j]`` — the
    gather costs no extra pass. Unallocated entries (-1) are clamped to page
    0; whatever garbage that block holds is killed by the caller's
    ``kv_pos = -1`` rows, exactly the invalid-slot contract the dense kernel
    already enforces (partially-filled tail pages work the same way).
    Returns (acc, m, l) like ``flash_decode_partial``."""
    B, KV, R, hd = q.shape
    NP, _, P, _ = k_pages.shape
    n_pp = page_table.shape[1]
    assert kv_pos.shape[1] == n_pp * P, (
        f"kv_pos covers {kv_pos.shape[1]} slots, table spans {n_pp * P}"
    )
    nk = n_pp
    scale = hd ** -0.5 if scale is None else scale

    kernel = functools.partial(
        _paged_kernel, kind=kind, window=window, sink=sink, scale=scale, nk=nk
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, KV, nk),
        in_specs=[
            pl.BlockSpec((1, 1, R, hd), lambda b, g, j, tbl: (b, g, 0, 0)),
            pl.BlockSpec(
                (1, 1, P, hd),
                lambda b, g, j, tbl: (jnp.maximum(tbl[b, j], 0), g, 0, 0),
            ),
            pl.BlockSpec(
                (1, 1, P, hd),
                lambda b, g, j, tbl: (jnp.maximum(tbl[b, j], 0), g, 0, 0),
            ),
            pl.BlockSpec((1, 1, 1, P), lambda b, g, j, tbl: (b, j, 0, 0)),
            pl.BlockSpec((1, R, 1), lambda b, g, j, tbl: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, R, hd), lambda b, g, j, tbl: (b, g, 0, 0)),
            pl.BlockSpec((1, 1, R, 1), lambda b, g, j, tbl: (b, g, 0, 0)),
            pl.BlockSpec((1, 1, R, 1), lambda b, g, j, tbl: (b, g, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((R, 1), jnp.float32),
            pltpu.VMEM((R, 1), jnp.float32),
            pltpu.VMEM((R, hd), jnp.float32),
        ],
    )
    acc, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=_out_shapes(B, KV, R, hd),
        interpret=interpret,
        name="flash_decode_paged_partial",
    )(
        page_table, q, k_pages, v_pages,
        kv_pos.reshape(B, nk, 1, P), q_pos[..., None],
    )
    return acc, m[..., 0], l[..., 0]


def flash_decode_partial(
    q: jax.Array,        # (B, KV, R, hd)
    k: jax.Array,        # (B, KV, S, hd)
    v: jax.Array,
    kv_pos: jax.Array,   # (B, S) int32
    q_pos: jax.Array,    # (B, R) int32
    *,
    kind: str = "causal",
    window: int = 0,
    sink: int = 0,
    block_s: int = 512,
    interpret: bool = False,
    scale: float | None = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    B, KV, R, hd = q.shape
    S = k.shape[2]
    blk = min(block_s, S)
    assert S % blk == 0, f"S={S} must be a multiple of block_s={blk} (pad in ops)"
    nk = S // blk
    scale = hd ** -0.5 if scale is None else scale

    kernel = functools.partial(
        _kernel, kind=kind, window=window, sink=sink, scale=scale, nk=nk
    )
    grid = (B, KV, nk)
    acc, m, l = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, R, hd), lambda b, g, j: (b, g, 0, 0)),
            pl.BlockSpec((1, 1, blk, hd), lambda b, g, j: (b, g, j, 0)),
            pl.BlockSpec((1, 1, blk, hd), lambda b, g, j: (b, g, j, 0)),
            pl.BlockSpec((1, 1, 1, blk), lambda b, g, j: (b, j, 0, 0)),
            pl.BlockSpec((1, R, 1), lambda b, g, j: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, R, hd), lambda b, g, j: (b, g, 0, 0)),
            pl.BlockSpec((1, 1, R, 1), lambda b, g, j: (b, g, 0, 0)),
            pl.BlockSpec((1, 1, R, 1), lambda b, g, j: (b, g, 0, 0)),
        ],
        out_shape=_out_shapes(B, KV, R, hd),
        scratch_shapes=[
            pltpu.VMEM((R, 1), jnp.float32),
            pltpu.VMEM((R, 1), jnp.float32),
            pltpu.VMEM((R, hd), jnp.float32),
        ],
        interpret=interpret,
        name="flash_decode_partial",
    )(q, k, v, kv_pos.reshape(B, nk, 1, blk), q_pos[..., None])
    return acc, m[..., 0], l[..., 0]
