"""jit'd wrappers: padding, layout, and the flash-decode + tree combine.

``verify_attention`` is the full TPU hot-spot op: cache partials from the
flash_decode kernel merged with staged-tree partials from the tree_attention
kernel — one logsumexp-consistent softmax over [cache ++ tree], identical to
ref.ref_verify_attention (and to models.attention.decode_attention).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_decode import flash_decode_paged_partial, flash_decode_partial
from repro.kernels.int8_matmul import Int8Weight, int8_matmul, quantize_rows
from repro.kernels.tree_attention import tree_attention_partial


def _pad_to(x: jax.Array, axis: int, multiple: int, value=0):
    n = x.shape[axis]
    pad = (-n) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


@functools.partial(
    jax.jit,
    static_argnames=("kind", "window", "sink", "block_s", "interpret"),
)
def verify_attention(
    q: jax.Array,        # (B, T, H, hd) staged queries
    k_cache: jax.Array,  # (B, S, KV, hd)
    v_cache: jax.Array,
    kv_pos: jax.Array,   # (B, S) int32 (-1 invalid)
    q_pos: jax.Array,    # (B, T)
    k_new: jax.Array,    # (B, T, KV, hd)
    v_new: jax.Array,
    tree_mask: jax.Array,    # (B, T, T) bool (incl. positional validity)
    *,
    kind: str = "causal",
    window: int = 0,
    sink: int = 0,
    block_s: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Returns (B, T, H, hd). TPU path for the verification step."""
    B, T, H, hd0 = q.shape
    KV = k_cache.shape[2]
    rep = H // KV

    # layout: (B, KV, rep*T, hd), rows ordered r*T + t; pad hd to 128
    qr = q.reshape(B, T, KV, rep, hd0).transpose(0, 2, 3, 1, 4).reshape(B, KV, rep * T, hd0)
    qr = _pad_to(qr, 3, 128)
    kc = _pad_to(k_cache.transpose(0, 2, 1, 3), 3, 128)   # (B, KV, S, hd)
    vc = _pad_to(v_cache.transpose(0, 2, 1, 3), 3, 128)
    kn = _pad_to(k_new.transpose(0, 2, 1, 3), 3, 128)
    vn = _pad_to(v_new.transpose(0, 2, 1, 3), 3, 128)
    hd = qr.shape[-1]

    # pad S to block multiple with invalid slots
    S = kc.shape[2]
    blk = min(block_s, S) if S else 1
    kc = _pad_to(kc, 2, blk)
    vc = _pad_to(vc, 2, blk)
    kvp = _pad_to(kv_pos, 1, blk, value=-1)

    qp_rows = jnp.tile(q_pos, (1, rep))                   # (B, rep*T)

    scale = hd0 ** -0.5
    acc_c, m_c, l_c = flash_decode_partial(
        qr, kc, vc, kvp, qp_rows,
        kind=kind, window=window, sink=sink, block_s=blk, interpret=interpret,
        scale=scale,
    )
    acc_d, m_d, l_d = tree_attention_partial(
        qr, kn, vn, tree_mask, interpret=interpret, scale=scale
    )

    m = jnp.maximum(m_c, m_d)
    cc = jnp.exp(m_c - m)[..., None]
    cd = jnp.exp(m_d - m)[..., None]
    out = (acc_c * cc + acc_d * cd) / jnp.maximum(
        (l_c[..., None] * cc + l_d[..., None] * cd), 1e-30
    )
    out = out[..., :hd0]                                  # drop hd padding
    out = out.reshape(B, KV, rep, T, hd0).transpose(0, 3, 1, 2, 4).reshape(B, T, H, hd0)
    return out


@functools.partial(
    jax.jit,
    static_argnames=("kind", "window", "sink", "interpret"),
)
def paged_verify_attention(
    q: jax.Array,           # (B, T, H, hd) staged queries
    k_pages: jax.Array,     # (NP, P, KV, hd) shared pool, model layout
    v_pages: jax.Array,
    page_table: jax.Array,  # (B, n_pp) int32 (-1 unallocated)
    kv_pos: jax.Array,      # (B, n_pp * P) int32 (-1 invalid)
    q_pos: jax.Array,       # (B, T)
    k_new: jax.Array,       # (B, T, KV, hd)
    v_new: jax.Array,
    tree_mask: jax.Array,   # (B, T, T) bool (incl. positional validity)
    *,
    kind: str = "causal",
    window: int = 0,
    sink: int = 0,
    interpret: bool = False,
) -> jax.Array:
    """Block-paged twin of ``verify_attention``: cache partials come from
    ``flash_decode_paged_partial`` (page table scalar-prefetched into the
    kernel's index_maps), the staged-tree partials and the logsumexp merge
    are byte-for-byte the dense path's — paging changes where committed KV
    lives, never how the two softmax halves combine."""
    B, T, H, hd0 = q.shape
    KV = k_pages.shape[2]
    rep = H // KV

    qr = q.reshape(B, T, KV, rep, hd0).transpose(0, 2, 3, 1, 4).reshape(B, KV, rep * T, hd0)
    qr = _pad_to(qr, 3, 128)
    kp = _pad_to(k_pages.transpose(0, 2, 1, 3), 3, 128)   # (NP, KV, P, hd)
    vp = _pad_to(v_pages.transpose(0, 2, 1, 3), 3, 128)
    kn = _pad_to(k_new.transpose(0, 2, 1, 3), 3, 128)
    vn = _pad_to(v_new.transpose(0, 2, 1, 3), 3, 128)

    qp_rows = jnp.tile(q_pos, (1, rep))                   # (B, rep*T)

    scale = hd0 ** -0.5
    acc_c, m_c, l_c = flash_decode_paged_partial(
        qr, kp, vp, page_table, kv_pos, qp_rows,
        kind=kind, window=window, sink=sink, interpret=interpret, scale=scale,
    )
    acc_d, m_d, l_d = tree_attention_partial(
        qr, kn, vn, tree_mask, interpret=interpret, scale=scale
    )

    m = jnp.maximum(m_c, m_d)
    cc = jnp.exp(m_c - m)[..., None]
    cd = jnp.exp(m_d - m)[..., None]
    out = (acc_c * cc + acc_d * cd) / jnp.maximum(
        (l_c[..., None] * cc + l_d[..., None] * cd), 1e-30
    )
    out = out[..., :hd0]
    out = out.reshape(B, KV, rep, T, hd0).transpose(0, 3, 1, 2, 4).reshape(B, T, H, hd0)
    return out


@functools.partial(jax.jit, static_argnames=("interpret", "k_axis"))
def quantized_matmul(
    x: jax.Array, w: Int8Weight, *, interpret: bool | None = None,
    k_axis: str | None = None,
) -> jax.Array:
    """W8A8 x @ w on the weight's int8 image, in ``x``'s dtype: the
    activations are quantized per row on each call, the weight was
    quantized once (``quantize_weight``). Pads to 128-tiles.

    ``interpret`` defaults to backend-aware: compiled on TPU, interpreter
    everywhere else (the kernel only lowers on TPU) — callers on TPU get
    the real kernel without remembering the flag. Pass an explicit bool to
    override (e.g. CPU parity tests force ``interpret=True``).

    ``k_axis``: inside ``shard_map`` with the contraction dim split over
    that mesh axis, the row scales are taken over the whole K (the column
    scales already are) and the float32 partial products summed across
    the axis — the unsplit result on every shard.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    M0, N0 = x.shape[0], w.q.shape[1]
    x_q, xs = quantize_rows(x, k_axis)
    x_q = _pad_to(_pad_to(x_q, 0, 128), 1, 128)
    xs = _pad_to(xs, 0, 128, value=1.0)
    w_q = _pad_to(_pad_to(w.q, 0, 128), 1, 128)
    ws = _pad_to(w.scale, 1, 128, value=1.0)
    out = int8_matmul(
        x_q, w_q, xs, ws, interpret=interpret,
        out_dtype=x.dtype if k_axis is None else jnp.float32,
    )[:M0, :N0]
    return out if k_axis is None else jax.lax.psum(out, k_axis).astype(x.dtype)
