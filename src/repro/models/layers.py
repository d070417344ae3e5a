"""Primitive layers: norms, RoPE, MLPs, embeddings. Pure functions over pytrees."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.int8_matmul import Int8Weight


def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + weight.astype(jnp.float32))).astype(dt)


# ---------------------------------------------------------------------- RoPE
def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    """Inverse frequencies, shape (head_dim // 2,), float32."""
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponent)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotate (..., S, H, head_dim) by per-token integer ``positions`` (..., S)."""
    dt = x.dtype
    hd = x.shape[-1]
    inv = rope_frequencies(hd, theta)                       # (hd/2,)
    ang = positions.astype(jnp.float32)[..., None] * inv    # (..., S, hd/2)
    cos = jnp.cos(ang)[..., None, :]                        # (..., S, 1, hd/2)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(dt)


# ----------------------------------------------------------------------- MLP
def _mm(x: jax.Array, w, quantize, w_axes=(None, None)) -> jax.Array:
    """(..., d) @ (d, f), optionally through the W8A8 Pallas kernel.

    ``quantize="int8"`` takes ``w`` as its int8 image
    (``kernels.int8_matmul.Int8Weight``, quantized once when the draft
    level was built) and routes the matmul through
    ``kernels.ops.quantized_matmul``, which quantizes only the activations
    (per row) — the ActivationQuant DSIA's TPU execution. Off-TPU the
    kernel runs interpreted, so CPU callers simulate with the dequantized
    image instead and never set the flag on hot paths.

    Under a mesh in context the partitioner cannot split the kernel, so it
    runs under ``shard_map`` with ``w`` split as ``w_axes`` pins it (batch
    over the data axes): a column split keeps its output columns, a
    contraction split sums the partials (the column scales already span
    the whole K; the row scales are taken across the split).
    """
    if quantize is None:
        return jnp.einsum("...d,df->...f", x, w)
    if quantize != "int8":
        raise ValueError(f"unsupported quantize mode {quantize!r}")
    if not isinstance(w, Int8Weight):
        raise TypeError(
            "quantize='int8' takes the weight's int8 image "
            "(kernels.int8_matmul.quantize_weight), not a float weight"
        )
    from repro.kernels.ops import quantized_matmul
    from repro.models.shard_utils import DATA_AXES, _mesh_axes, resolve_spec

    def mm(x, w, k_axis=None):
        lead = x.shape[:-1]
        out = quantized_matmul(x.reshape(-1, x.shape[-1]), w, k_axis=k_axis)
        return out.reshape(*lead, out.shape[-1])

    if not _mesh_axes():
        return mm(x, w)
    from jax.sharding import PartitionSpec as P

    wk, wn = resolve_spec(w.q.shape, *w_axes)
    batch = resolve_spec(x.shape[:1], DATA_AXES) + (None,) * (x.ndim - 2)
    if wk is not None:
        mm = functools.partial(mm, k_axis=wk)
    return jax.shard_map(
        mm, in_specs=(P(*batch, wk), Int8Weight(P(wk, wn), P(None, wn))),
        out_specs=P(*batch, wn), check_vma=False,
    )(x, w)


def _pin(w, k_axis, n_axis):
    """``constrain_full`` for a float weight or an int8 image (its scales
    follow the weight's columns)."""
    from repro.models.shard_utils import constrain_full

    if isinstance(w, Int8Weight):
        return Int8Weight(constrain_full(w.q, k_axis, n_axis),
                          constrain_full(w.scale, None, n_axis))
    return constrain_full(w, k_axis, n_axis)


def mlp_apply(
    params: dict, x: jax.Array, act: str, gated: bool, quantize=None
) -> jax.Array:
    """SwiGLU/GeGLU (gated) or plain 2-matrix MLP.

    Weights are pinned to their TP spec at the use site so FSDP-stored
    shards are gathered over 'data' (cheap) rather than the activations.
    ``quantize`` routes the three projections through the W8A8 kernel (the
    MLP carries the bulk of the stack's matmul FLOPs; attention projections
    and the LM head stay in the model dtype).
    """
    fn = jax.nn.silu if act == "silu" else jax.nn.gelu
    col, row = (None, "model"), ("model", None)
    w_up = _pin(params["w_up"], *col)
    w_down = _pin(params["w_down"], *row)
    if gated:
        w_gate = _pin(params["w_gate"], *col)
        g = fn(_mm(x, w_gate, quantize, col))
        u = _mm(x, w_up, quantize, col)
        return _mm(g * u, w_down, quantize, row)
    h = fn(_mm(x, w_up, quantize, col))
    return _mm(h, w_down, quantize, row)


def mlp_init(key: jax.Array, d_model: int, d_ff: int, gated: bool, dtype) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    scale_in = d_model ** -0.5
    scale_out = d_ff ** -0.5
    p = {
        "w_up": (jax.random.normal(k1, (d_model, d_ff)) * scale_in).astype(dtype),
        "w_down": (jax.random.normal(k2, (d_ff, d_model)) * scale_out).astype(dtype),
    }
    if gated:
        p["w_gate"] = (jax.random.normal(k3, (d_model, d_ff)) * scale_in).astype(dtype)
    return p


# ----------------------------------------------------------------- embeddings
def embed_tokens(embedding: jax.Array, tokens: jax.Array) -> jax.Array:
    return jnp.take(embedding, tokens, axis=0)


def unembed(x: jax.Array, head: jax.Array) -> jax.Array:
    """(..., d) @ (d, V) -> logits in float32."""
    return jnp.einsum("...d,dv->...v", x.astype(jnp.float32), head.astype(jnp.float32))
