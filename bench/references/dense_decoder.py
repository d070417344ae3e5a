"""Plain reference of the dense decoder that both configurations serve.

Straightforward ``jax.numpy`` in float32 at ``precision="highest"``, one
sequence at a time, one layer per jitted call, with no cache, no batching,
no kernels and nothing imported from the program under test. It reads the
benchmark's own weight pytree (``harness/weights.py``). The equations:

    h_0 = E[t]
    x   = rms(h) * (1 + g1)                 rms(z) = z / sqrt(mean(z^2) + eps)
    q, k, v = x Wq, x Wk, x Wv              (heads; KV head j serves query
                                             heads j*rep .. j*rep + rep - 1)
    q, k <- RoPE(pos), rotating the two halves of each head by pos * theta^(-2i/hd)
    a   = softmax(q k^T / sqrt(hd) + causal) v
    h  += a Wo
    x   = rms(h) * (1 + g2)
    h  += act(x Wg) * (x Wu) Wd   (gated)   or   act(x Wu) Wd
    logits = (rms(h) * (1 + gf)) W_head, over the real vocabulary

``act`` is SiLU or the tanh form of GELU. Departures from the published
models are listed in each configuration's ``assumed``.

``precision`` other than "f32" gives the control: every projection (and
the head) runs with int8 weights per output column and int8 activations per
row ("int8"), or with both rounded to float8 e4m3 on the same scales
("fp8"), accumulating in float32.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


def _quant(x: jax.Array, axis: int, precision: str) -> jax.Array:
    """Round ``x`` to the control's grid along ``axis`` (scales kept)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    if precision == "int8":
        s = jnp.maximum(amax, 1e-30) / 127.0
        return jnp.clip(jnp.round(x / s), -127, 127) * s
    if precision == "fp8":
        s = jnp.maximum(amax, 1e-30) / 448.0
        return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    raise ValueError(f"unknown precision {precision!r}")


def _mm(x: jax.Array, w: jax.Array, precision: str) -> jax.Array:
    """x (S, K) @ w (K, N) in float32, or on the control's grid."""
    w = w.astype(jnp.float32)
    if precision != "f32":
        x = _quant(x, -1, precision)
        w = _quant(w, 0, precision)
    return jnp.dot(x, w, precision=HI)


def _rms(h: jax.Array, g: jax.Array, eps: float) -> jax.Array:
    var = jnp.mean(h * h, axis=-1, keepdims=True)
    return h * jax.lax.rsqrt(var + eps) * (1.0 + g.astype(jnp.float32))


def _rope(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos.astype(jnp.float32)[:, None] * inv            # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _act(name: str, x: jax.Array) -> jax.Array:
    if name == "silu":
        return x * jax.nn.sigmoid(x)
    if name == "gelu":
        return 0.5 * x * (1.0 + jnp.tanh(
            np.sqrt(2.0 / np.pi) * (x + 0.044715 * x ** 3)))
    raise ValueError(f"unknown activation {name!r}")


def _layer(c: dict, precision: str, h, stacked, i):
    p = jax.tree.map(lambda a: a[i], stacked)
    S, d = h.shape
    H, KV, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    eps, theta = c["norm_epsilon"], c["rope_theta"]
    pos = jnp.arange(S)
    x = _rms(h, p["norm1"], eps)
    a = p["attn"]
    q = _mm(x, a["wq"].reshape(d, H * hd), precision).reshape(S, H, hd)
    k = _mm(x, a["wk"].reshape(d, KV * hd), precision).reshape(S, KV, hd)
    v = _mm(x, a["wv"].reshape(d, KV * hd), precision).reshape(S, KV, hd)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    k = jnp.repeat(k, H // KV, axis=1)
    v = jnp.repeat(v, H // KV, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) * hd ** -0.5
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v,
                   precision=HI)
    h = h + _mm(o.reshape(S, H * hd), a["wo"].reshape(H * hd, d), precision)
    x = _rms(h, p["norm2"], eps)
    m = p["mlp"]
    if c["mlp_gated"]:
        y = (_act(c["hidden_act"], _mm(x, m["w_gate"], precision))
             * _mm(x, m["w_up"], precision))
    else:
        y = _act(c["hidden_act"], _mm(x, m["w_up"], precision))
    return h + _mm(y, m["w_down"], precision)


def _head(c: dict, precision: str, h, final_norm, lm_head, targets):
    """Per position: best logit, the logit of ``targets``, the argmax."""
    x = _rms(h, final_norm, c["norm_epsilon"])
    lg = _mm(x, lm_head, precision)[:, : c["vocab_size"]]
    at = jnp.take_along_axis(lg, targets[:, None], axis=1)[:, 0]
    return jnp.max(lg, axis=-1), at, jnp.argmax(lg, axis=-1)


class Reference:
    """Scores sequences against the reference (or the control)."""

    def __init__(self, c: dict, precision: str = "f32"):
        self.c, self.precision = c, precision
        self._layer = jax.jit(functools.partial(_layer, c, precision))
        self._head = jax.jit(functools.partial(_head, c, precision))

    def score(self, params: dict, tokens: np.ndarray, start: int,
              targets: np.ndarray, pad_to: int = 256
              ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Run ``tokens``; at positions ``start .. start + n - 1`` (n =
        len(targets)) return the best logit, the logit of each target and
        the argmax. Sequences are padded to a multiple of ``pad_to`` (the
        causal mask keeps padding out of earlier positions)."""
        S = len(tokens)
        Sp = -(-S // pad_to) * pad_to
        tok = np.zeros(Sp, np.int32)
        tok[:S] = tokens
        h = jnp.take(params["embed"], jnp.asarray(tok), axis=0).astype(
            jnp.float32)
        stacked = params["segments"][0][0]
        for i in range(self.c["num_hidden_layers"]):
            h = self._layer(h, stacked, jnp.int32(i))
        n = len(targets)
        tgt = np.zeros(Sp, np.int32)
        tgt[start:start + n] = targets
        best, at, arg = self._head(h, params["final_norm"], params["lm_head"],
                                   jnp.asarray(tgt))
        sl = slice(start, start + n)
        return (np.asarray(best)[sl], np.asarray(at)[sl],
                np.asarray(arg)[sl])
