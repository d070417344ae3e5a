"""ActivationQuant DSIA numerics contract: one int8 image of the MLP
weights (per layer, per column over the whole K), made once when the
draft bank is built. The Pallas W8A8 path (``kernels.quantized_matmul``,
interpret mode off-TPU) runs on it; the CPU simulation
(``engine.fake_quant_int8``) is its dequantized image. The two must agree
within tolerance — one contract, two executions, so a cascade level drafts
the same way wherever the bank materialized it."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import get_config
from repro.core.dsia import build_hierarchy
from repro.core.engine import fake_quant_int8
from repro.kernels import ref as R
from repro.kernels.int8_matmul import Int8Weight, quantize_cols, quantize_rows, quantize_weight
from repro.kernels.ops import quantized_matmul
from repro.models import layers
from repro.models import model as M
from repro.models.layers import mlp_apply, mlp_init
from repro.serving.draft_bank import DraftBank

CFG = dataclasses.replace(get_config("vicuna-7b").reduced(), num_layers=2)
PARAMS = M.init_params(CFG, jax.random.PRNGKey(0))
QPARAMS = M.int8_mlp_params(PARAMS)


def _mlp_weights(params):
    return [(k, w) for seg in params["segments"] for layer in seg
            for k, w in layer.get("mlp", {}).items()]


def _rel(a, b):
    return float(jnp.linalg.norm(a.astype(jnp.float32) - b.astype(jnp.float32))
                 / jnp.maximum(jnp.linalg.norm(b.astype(jnp.float32)), 1e-9))


def test_quantized_matmul_recovers_fake_quant_grid():
    """The kernel on a weight's int8 image computes x @ (the dequantized
    image) up to the dynamic per-row ACTIVATION quantization (<~1%)."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(8, 64)).astype(np.float32)) * 2.0
    w = jnp.asarray(rng.normal(size=(64, 96)).astype(np.float32))
    image = quantize_weight(w)
    out = quantized_matmul(x, image, interpret=True)
    assert _rel(out, x @ image.dequantize(jnp.float32)) < 0.02


def test_fake_quant_is_idempotent_and_per_output_channel():
    """Per output channel AND per layer: a layer with weights 100x larger
    does not coarsen its neighbour's grid. Idempotent, and every leaf that
    is not a dense-MLP weight is the target's own object."""
    seg = PARAMS["segments"][0]
    w = seg[0]["mlp"]["w_up"]
    loud = {**PARAMS, "segments": [[{**seg[0], "mlp": {
        **seg[0]["mlp"], "w_up": w.at[0].multiply(100.0)}}]]}
    w1 = fake_quant_int8(loud)["segments"][0][0]["mlp"]["w_up"]
    w2 = fake_quant_int8(fake_quant_int8(loud))["segments"][0][0]["mlp"]["w_up"]
    np.testing.assert_allclose(np.asarray(w1), np.asarray(w2), rtol=0, atol=1e-4)
    for r in range(w.shape[0]):
        assert _rel(w1[r], loud["segments"][0][0]["mlp"]["w_up"][r]) < 0.01
    sim = fake_quant_int8(PARAMS)
    for path, leaf in jax.tree_util.tree_leaves_with_path(PARAMS):
        got = sim
        for k in path:
            got = got[k.key if hasattr(k, "key") else k.idx]
        assert (got is leaf) != ("mlp" in jax.tree_util.keystr(path))


def test_mlp_apply_kernel_vs_sim():
    """The MLP forward — the path the bank actually routes — under
    ``quantize="int8"`` (kernel, int8 image) vs its dequantized image
    (sim)."""
    rng = np.random.default_rng(2)
    p = mlp_init(jax.random.PRNGKey(3), 32, 64, True, jnp.float32)
    x = jnp.asarray(rng.normal(size=(4, 6, 32)).astype(np.float32))
    image = {k: quantize_weight(w) for k, w in p.items()}
    out_kernel = mlp_apply(image, x, "silu", True, quantize="int8")
    out_sim = mlp_apply({k: v.dequantize(jnp.float32) for k, v in image.items()},
                        x, "silu", True)
    ref = mlp_apply(p, x, "silu", True)
    assert _rel(out_kernel, ref) < 0.05
    assert _rel(out_sim, ref) < 0.05
    assert _rel(out_kernel, out_sim) < 0.06
    with pytest.raises(TypeError, match="int8 image"):
        mlp_apply(p, x, "silu", True, quantize="int8")


def test_mlp_apply_rejects_unknown_quantize():
    p = mlp_init(jax.random.PRNGKey(0), 16, 32, False, jnp.float32)
    with pytest.raises(ValueError, match="unsupported quantize"):
        mlp_apply(p, jnp.ones((2, 16)), "silu", False, quantize="int4")


def test_chain_draft_scan_honors_level_execution():
    """The generalized chain scan executes per-level quantize and
    attn_override (not just gates): its first drafted token must equal the
    argmax of a direct decode under the SAME execution flags."""
    import functools

    from repro.core.engine import chain_draft_scan

    rng = np.random.default_rng(5)
    cache = M.init_cache(CFG, 1, 64)
    prompt = jnp.asarray(rng.integers(2, CFG.vocab_size, size=(1, 12)), jnp.int32)
    last, cache = M.prefill(CFG, PARAMS, {"tokens": prompt}, cache)
    pending = jnp.argmax(last, -1).astype(jnp.int32)
    override = {"kind": "streaming", "window": 8, "sink": 2}
    fn = jax.jit(functools.partial(
        chain_draft_scan, CFG, 2, quantize="int8", attn_override=override
    ))
    chains, have = fn(
        QPARAMS, cache, pending, jnp.zeros((1, 4), jnp.int32),
        jnp.zeros((1,), jnp.int32), jnp.full((1,), 2, jnp.int32), None,
    )
    assert int(np.asarray(have)[0]) == 2
    lg, _ = M.decode_step(CFG, QPARAMS, cache, pending[:, None],
                          quantize="int8", attn_override=override)
    assert int(np.asarray(chains)[0, 0]) == int(jnp.argmax(lg[0, 0]))


def test_decode_step_int8_kernel_vs_sim():
    """Whole-model contract on a tiny model: decode against the same
    (target-committed) cache with ``quantize="int8"`` vs fake-quant params.
    The two int8 executions must be closer to each other than either is
    allowed to drift overall, and their greedy argmaxes must agree almost
    everywhere (drafting only consumes the argmax)."""
    rng = np.random.default_rng(4)
    cache = M.init_cache(CFG, 1, 64)
    prompt = jnp.asarray(rng.integers(2, CFG.vocab_size, size=(1, 12)), jnp.int32)
    _, cache = M.prefill(CFG, PARAMS, {"tokens": prompt}, cache)
    toks = jnp.asarray(rng.integers(2, CFG.vocab_size, size=(1, 6)), jnp.int32)
    lg_kernel, _ = M.decode_step(CFG, QPARAMS, cache, toks, quantize="int8")
    lg_sim, _ = M.decode_step(CFG, fake_quant_int8(PARAMS), cache, toks)
    assert _rel(lg_kernel, lg_sim) < 0.10
    agree = float((jnp.argmax(lg_kernel, -1) == jnp.argmax(lg_sim, -1)).mean())
    assert agree >= 0.75


def test_kernel_bank_holds_int8_mlp_images():
    """``int8_exec="kernel"``: the int8 level owns int8 (R, K, N) MLP
    weights with per-layer (R, 1, N) float32 scales; every other leaf is
    the target's own object; ``param_bytes`` counts exactly the images."""
    bank = DraftBank(CFG, PARAMS, build_hierarchy(CFG, "mixing"),
                     int8_exec="kernel")
    cheap = bank.drafter
    assert cheap.quantize == "int8" and cheap.owns_params
    images = _mlp_weights(cheap.params)
    assert {k for k, _ in images} == {"w_up", "w_down", "w_gate"}
    for (_, image), (_, w) in zip(images, _mlp_weights(PARAMS)):
        assert isinstance(image, Int8Weight)
        R_, K, N = w.shape
        assert image.q.dtype == jnp.int8 and image.q.shape == (R_, K, N)
        assert image.scale.dtype == jnp.float32 and image.scale.shape == (R_, 1, N)
        for r in range(R_):
            q, s = quantize_cols(w[r])
            assert np.array_equal(np.asarray(image.q[r]), np.asarray(q))
            np.testing.assert_allclose(np.asarray(image.scale[r]), np.asarray(s),
                                       rtol=1e-6)
    image_leaves = jax.tree.leaves([image for _, image in images])
    target = {id(leaf) for leaf in jax.tree.leaves(PARAMS)}
    mlp = {id(leaf) for leaf in image_leaves}
    for leaf in jax.tree.leaves(cheap.params):
        assert (id(leaf) in mlp) != (id(leaf) in target)
    assert bank.param_bytes == sum(leaf.nbytes for leaf in image_leaves)


def _per_call_mm(x, w, quantize, w_axes=(None, None)):
    """The W8A8 matmul as it ran before the bank held int8 images: the
    float weight quantized per column on every call."""
    if quantize is None:
        return jnp.einsum("...d,df->...f", x, w)
    xq, xs = quantize_rows(x.reshape(-1, x.shape[-1]))
    wq, ws = quantize_cols(w)
    out = R.ref_int8_matmul(xq, wq, xs, ws).astype(x.dtype)
    return out.reshape(*x.shape[:-1], w.shape[-1])


def test_prequantized_drafter_drafts_as_per_call_quantization(monkeypatch):
    """The interpreted drafter on the bank's int8 images drafts the same
    tokens as quantizing the float weights on every call."""
    import functools

    from repro.core.engine import chain_draft_scan

    rng = np.random.default_rng(6)
    B = 2
    cache = M.init_cache(CFG, B, 64)
    prompt = jnp.asarray(rng.integers(2, CFG.vocab_size, size=(B, 12)), jnp.int32)
    last, cache = M.prefill(CFG, PARAMS, {"tokens": prompt}, cache)
    pending = jnp.argmax(last, -1).astype(jnp.int32)
    bank = DraftBank(CFG, PARAMS, build_hierarchy(CFG, "mixing"),
                     int8_exec="kernel")
    gates = jnp.asarray(bank.drafter.gates)

    def draft(params):
        fn = jax.jit(functools.partial(chain_draft_scan, CFG, 4, quantize="int8"))
        chains, _ = fn(params, cache, pending, jnp.zeros((B, 4), jnp.int32),
                       jnp.zeros((B,), jnp.int32), jnp.full((B,), 4, jnp.int32),
                       gates)
        return np.asarray(chains)

    prequantized = draft(bank.drafter.params)
    monkeypatch.setattr(layers, "_mm", _per_call_mm)
    np.testing.assert_array_equal(prequantized, draft(PARAMS))
