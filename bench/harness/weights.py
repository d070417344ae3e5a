"""Random weights of a dense decoder, made on the device in one jitted call.

The benchmark makes the weights itself, from the configuration's own
``weight_seed`` (never from ``--seed``: a deployment serves one model), in
the type they are served in, and in the pytree the server takes:

    embed (V', d)   final_norm (d,)   lm_head (d, V')
    segments = [[{norm1, attn {wq, wk, wv, wo}, norm2,
                  mlp {w_up, w_down[, w_gate]}}]]   leaves stacked (L, ...)

where V' is the vocabulary padded to a multiple of 256. Layers (and row
blocks of the embedding and head) are drawn one at a time inside a
``lax.map``, so no float32 temporary holds more than one of them. Scales
follow 1/sqrt(fan-in); norm scales are small random offsets (the served
norm multiplies by 1 + w).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def padded_vocab(vocab: int) -> int:
    return -(-vocab // 256) * 256


def shapes(c: dict) -> dict:
    """Leaf shapes of one layer and of the top level."""
    d, f = c["hidden_size"], c["intermediate_size"]
    h, kv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    mlp = {"w_up": (d, f), "w_down": (f, d)}
    if c["mlp_gated"]:
        mlp["w_gate"] = (d, f)
    layer = {"norm1": (d,), "norm2": (d,),
             "attn": {"wq": (d, h, hd), "wk": (d, kv, hd), "wv": (d, kv, hd),
                      "wo": (h, hd, d)},
             "mlp": mlp}
    vp = padded_vocab(c["vocab_size"])
    return {"embed": (vp, d), "final_norm": (d,), "lm_head": (d, vp),
            "layer": layer}


def _fan_in(name: str, shape: tuple) -> int:
    if name == "wo":
        return shape[0] * shape[1]
    if name == "embed":
        return shape[1]
    return shape[0]


def _draw(key, name: str, shape: tuple, dtype, fan_in: tuple = None):
    if name.startswith("norm") or name == "final_norm":
        return (0.1 * jax.random.normal(key, shape)).astype(dtype)
    scale = _fan_in(name, fan_in or shape) ** -0.5
    return (scale * jax.random.normal(key, shape)).astype(dtype)


def _chunked(key, name: str, shape: tuple, dtype, chunks: int = 8):
    """A large 2-d leaf drawn in row blocks, so the float32 draw of only
    one block exists at a time."""
    rows = shape[0] // chunks
    blocks = jax.lax.map(
        lambda k: _draw(k, name, (rows, shape[1]), dtype, fan_in=shape),
        jax.random.split(key, chunks))
    return blocks.reshape(shape)


def _layer(c: dict, key, dtype) -> dict:
    spec = shapes(c)["layer"]
    flat = [("norm1", spec["norm1"]), ("norm2", spec["norm2"])]
    flat += [(k, v) for k, v in spec["attn"].items()]
    flat += [(k, v) for k, v in spec["mlp"].items()]
    keys = jax.random.split(key, len(flat))
    vals = {n: _draw(k, n, s, dtype) for (n, s), k in zip(flat, keys)}
    return {"norm1": vals["norm1"], "norm2": vals["norm2"],
            "attn": {k: vals[k] for k in spec["attn"]},
            "mlp": {k: vals[k] for k in spec["mlp"]}}


def make(c: dict, out_shardings: Optional[dict] = None) -> dict:
    """The whole parameter pytree, on the device, in ``torch_dtype``."""
    dtype = jnp.dtype(c["torch_dtype"])
    top = shapes(c)
    n_layers = c["num_hidden_layers"]

    def build(key):
        k_embed, k_norm, k_head, k_layers = jax.random.split(key, 4)
        layers = jax.lax.map(lambda k: _layer(c, k, dtype),
                             jax.random.split(k_layers, n_layers))
        return {"embed": _chunked(k_embed, "embed", top["embed"], dtype),
                "final_norm": _draw(k_norm, "final_norm", top["final_norm"],
                                    dtype),
                "lm_head": _chunked(k_head, "lm_head", top["lm_head"], dtype),
                "segments": [[layers]]}

    fn = jax.jit(build, out_shardings=out_shardings)
    return fn(jax.random.PRNGKey(int(c["weight_seed"])))
