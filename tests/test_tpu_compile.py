"""The Pallas kernels of the served path, compiled for a described TPU v5e.

Interpret mode (every other kernel test) cannot see what the TPU compiler
refuses: block shapes off the (8, 128) tiling, in-kernel gathers, a Mosaic
kernel the SPMD partitioner would have to split. These tests compile each
kernel at real widths (starcoder2-3b: KV=2, rep=12, a 32-node tree bucket,
d_model 3072 x d_ff 12288; internlm2-20b heads on a 4-chip mesh) for a
``v5e:2x2`` topology that is described, not attached. Nothing runs.

The topology is described inside a module fixture — never at import — so
only the worker that runs this file loads the TPU compiler.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.kernels.flash_decode import flash_decode_paged_partial, flash_decode_partial
from repro.kernels.int8_matmul import Int8Weight
from repro.kernels.ops import paged_verify_attention, quantized_matmul, verify_attention
from repro.kernels.tree_attention import tree_attention_partial

B, KV, REP, T, HD = 8, 2, 12, 32, 128          # starcoder2-3b, tree bucket 32
R, H = REP * T, KV * REP
S_CACHE, PAGE = 1024, 64


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prev_cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            desc = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as e:
            desc, reason = None, f"no v5e:2x2 topology can be described: {e}"
        if desc is not None:
            yield desc
    jax.config.update("jax_enable_compilation_cache", prev_cache)
    compilation_cache.reset_cache()
    if desc is None:
        pytest.skip(reason)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_tree_attention_compiles(one_chip):
    bf = functools.partial(_spec, dtype=jnp.bfloat16, sharding=one_chip)
    text = _compiled_text(
        tree_attention_partial,
        bf((B, KV, R, HD)), bf((B, KV, T, HD)), bf((B, KV, T, HD)),
        _spec((B, T, T), jnp.bool_, one_chip),
    )
    assert "tpu_custom_call" in text


def test_flash_decode_compiles(one_chip):
    bf = functools.partial(_spec, dtype=jnp.bfloat16, sharding=one_chip)
    i32 = functools.partial(_spec, dtype=jnp.int32, sharding=one_chip)
    text = _compiled_text(
        flash_decode_partial,
        bf((B, KV, R, HD)), bf((B, KV, S_CACHE, HD)), bf((B, KV, S_CACHE, HD)),
        i32((B, S_CACHE)), i32((B, R)),
    )
    assert "tpu_custom_call" in text


def test_flash_decode_paged_compiles(one_chip):
    bf = functools.partial(_spec, dtype=jnp.bfloat16, sharding=one_chip)
    i32 = functools.partial(_spec, dtype=jnp.int32, sharding=one_chip)
    n_pp = S_CACHE // PAGE
    text = _compiled_text(
        flash_decode_paged_partial,
        bf((B, KV, R, HD)), bf((B * n_pp, KV, PAGE, HD)),
        bf((B * n_pp, KV, PAGE, HD)), i32((B, n_pp)), i32((B, S_CACHE)),
        i32((B, R)),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_verify_ops_compile(one_chip, paged):
    """The exported verify ops: both kernels plus the logsumexp merge."""
    bf = functools.partial(_spec, dtype=jnp.bfloat16, sharding=one_chip)
    i32 = functools.partial(_spec, dtype=jnp.int32, sharding=one_chip)
    staged = (bf((B, T, KV, HD)), bf((B, T, KV, HD)),
              _spec((B, T, T), jnp.bool_, one_chip))
    if paged:
        n_pp = S_CACHE // PAGE
        args = (bf((B, T, H, HD)), bf((B * n_pp, PAGE, KV, HD)),
                bf((B * n_pp, PAGE, KV, HD)), i32((B, n_pp)),
                i32((B, S_CACHE)), i32((B, T))) + staged
        text = _compiled_text(paged_verify_attention, *args)
    else:
        args = (bf((B, T, H, HD)), bf((B, S_CACHE, KV, HD)),
                bf((B, S_CACHE, KV, HD)), i32((B, S_CACHE)),
                i32((B, T))) + staged
        text = _compiled_text(verify_attention, *args)
    assert text.count("tpu_custom_call") >= 2


def test_quantized_matmul_compiles(one_chip):
    """W8A8 at the starcoder2-3b MLP widths (the cascade's int8 level:
    w_up, then w_down), on the weight's int8 image, with the blocks
    ``choose_blocks`` picks for a draft step's rows."""
    for K, N in [(3072, 12288), (12288, 3072)]:
        text = _compiled_text(
            functools.partial(quantized_matmul, interpret=False),
            _spec((B * T, K), jnp.bfloat16, one_chip),
            Int8Weight(_spec((K, N), jnp.int8, one_chip),
                       _spec((1, N), jnp.float32, one_chip)),
        )
        assert "tpu_custom_call" in text


@pytest.fixture
def mesh4(topo, monkeypatch):
    """The described 2x2 chips as a model=4 mesh. The model picks compiled
    kernels by asking for the backend, which is the CPU here."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    return Mesh(np.asarray(topo.devices).reshape(1, 4), ("data", "model"),
                axis_types=(jax.sharding.AxisType.Auto,) * 2)


def _mesh_spec(mesh, shape, dtype, *spec):
    return _spec(shape, dtype, NamedSharding(mesh, P(*spec)))


def test_tree_pass_on_mesh_needs_no_collective(mesh4):
    """internlm2-20b heads (H=48, KV=8) on model=4: the staged tree pass
    runs the kernel under shard_map over the KV heads, so the compiled
    program has the kernel and no collective around it."""
    from repro.models.attention import decode_attention

    b, h, kv, s = 8, 48, 8, 1024
    sd = functools.partial(_mesh_spec, mesh4)
    heads = (None, None, "model")
    args = (sd((b, T, h, HD), jnp.bfloat16, *heads),
            sd((b, s, kv, HD), jnp.bfloat16, *heads),
            sd((b, s, kv, HD), jnp.bfloat16, *heads),
            sd((b,), jnp.int32),
            sd((b, T, kv, HD), jnp.bfloat16, *heads),
            sd((b, T, kv, HD), jnp.bfloat16, *heads),
            sd((b, T), jnp.int32),
            sd((b, T, T), jnp.bool_))

    def verify(q, kc, vc, pos, kn, vn, qp, tm):
        return decode_attention(q, kc, vc, pos, kn, vn, qp, tree_mask=tm,
                                backend="pallas")

    with jax.sharding.set_mesh(mesh4):
        text = _compiled_text(verify, *args)
    assert "tpu_custom_call" in text
    assert not re.search(r"all-gather|all-reduce|all-to-all", text)


def test_int8_mlp_on_mesh_gathers_no_weights(mesh4):
    """The cascade's int8 level on model=4 at internlm2-20b widths (gated
    SiLU MLP, 6144 x 16384, int8 images): all three W8A8 matmuls compile
    per shard. The only collectives are the all-reduces of the down
    projection's contraction split (the activations' whole-K row scales
    and the partial sums), never a gather of the weights."""
    from repro.models.layers import mlp_apply

    d, f = 6144, 16384
    def sd(shape, *spec):
        return _mesh_spec(mesh4, shape, jnp.bfloat16, *spec)

    def image(shape, k, n):
        return Int8Weight(
            _mesh_spec(mesh4, shape, jnp.int8, k, n),
            _mesh_spec(mesh4, (1, shape[1]), jnp.float32, None, n))

    p = {"w_up": image((d, f), None, "model"),
         "w_gate": image((d, f), None, "model"),
         "w_down": image((f, d), "model", None)}
    mlp = functools.partial(mlp_apply, act="silu", gated=True, quantize="int8")
    with jax.sharding.set_mesh(mesh4):
        text = _compiled_text(mlp, p, sd((B, T, d)))
    assert text.count("tpu_custom_call") >= 3
    assert "all-reduce" in text
    assert not re.search(r"all-gather|all-to-all", text)


def test_int8_drafter_kernel_reads_its_weight_from_hbm(one_chip, monkeypatch):
    """The int8 drafter's decode step at starcoder2-3b widths (two layers,
    scanned): every W8A8 kernel call takes its layer's int8 weight in HBM.
    No other op stages the weight in VMEM (``S(1)``) ahead of the kernel,
    so the kernel's own time covers reading it, as its roofline counts."""
    import dataclasses

    from repro.config import get_config
    from repro.models import model as M

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(get_config("starcoder2-3b"), num_layers=2)

    def on_chip(a):
        return _spec(a.shape, a.dtype, one_chip)

    params = jax.eval_shape(lambda k: M.init_params(cfg, k), jax.random.PRNGKey(0))
    qparams = jax.tree.map(on_chip, jax.eval_shape(M.int8_mlp_params, params))
    cache = jax.tree.map(on_chip, jax.eval_shape(
        lambda: M.init_cache(cfg, B, 256, dtype=jnp.bfloat16, paged=True,
                             page_size=PAGE)))
    step = functools.partial(M.decode_step, cfg, quantize="int8")
    text = _compiled_text(lambda p, c, t: step(p, c, t)[0], qparams, cache,
                          _spec((B, T), jnp.int32, one_chip))
    layouts = dict(re.findall(r"^\s*(?:ROOT )?%([\w.\-]+) = (\S+)", text, re.M))
    calls = [line for line in text.splitlines()
             if "int8_matmul" in line and "custom-call(" in line]
    assert len(calls) == 2
    for line in calls:
        weight = re.search(r"custom-call\(([^)]*)\)", line).group(1).split(",")[1]
        layout = layouts[weight.strip().lstrip("%")]
        assert layout.startswith("s8[") and "S(1)" not in layout, (weight, layout)
