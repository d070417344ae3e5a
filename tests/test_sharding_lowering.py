"""Sharding-rule + dry-run machinery tests on a small forced-device mesh.

Runs in a SUBPROCESS because the device count must be forced before jax
initializes (and the rest of the suite must see the single real device).
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import functools, json
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.config import get_config, get_shape
    from repro.config.base import InputShape
    from repro.launch import sharding as SH
    from repro.launch.mesh import make_mesh
    from repro.models import model as M

    mesh = make_mesh((2, 4), ("data", "model"))
    jax.sharding.set_mesh(mesh)
    results = {}
    for arch in ["gemma3-1b", "qwen2-moe-a2.7b", "mamba2-130m"]:
        cfg = get_config(arch).reduced()
        pshape = jax.eval_shape(functools.partial(M.init_params, cfg), jax.random.key(0))
        pspec = SH.param_specs(cfg, mesh)
        psh = jax.tree.map(lambda p: NamedSharding(mesh, p), pspec,
                           is_leaf=lambda x: isinstance(x, P))
        cshape = jax.eval_shape(functools.partial(M.init_cache, cfg, 8, 64))
        cspec = SH.cache_specs(cfg, mesh)
        csh = jax.tree.map(lambda p: NamedSharding(mesh, p), cspec,
                           is_leaf=lambda x: isinstance(x, P))
        toks = jax.ShapeDtypeStruct((8, 4), jnp.int32)

        def serve(params, cache, tokens):
            logits, staged = M.decode_step(cfg, params, cache, tokens)
            cache2 = M.commit_cache(cfg, cache, staged,
                                    jnp.arange(4), jnp.full((8,), 2, jnp.int32))
            return jnp.argmax(logits, -1), cache2

        fn = jax.jit(serve, in_shardings=(psh, csh, NamedSharding(mesh, P("data", None))))
        compiled = fn.lower(pshape, cshape, toks).compile()
        results[arch] = compiled.memory_analysis().temp_size_in_bytes
    print(json.dumps(results))
    """
)


@pytest.mark.slow
def test_small_mesh_serve_lowering():
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT], capture_output=True, text=True,
        env=env, timeout=540,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert set(res) == {"gemma3-1b", "qwen2-moe-a2.7b", "mamba2-130m"}
    assert all(v > 0 for v in res.values())


def test_param_specs_congruent_with_params():
    """Spec tree must be congruent with the real param pytree for jit."""
    import functools

    import jax
    from jax.sharding import PartitionSpec as P

    from repro.config import get_config
    from repro.launch import sharding as SH
    from repro.launch.mesh import make_host_mesh
    from repro.models import model as M

    mesh = make_host_mesh()
    for arch in ["mixtral-8x22b", "jamba-v0.1-52b", "musicgen-medium",
                 "llava-next-mistral-7b", "starcoder2-3b"]:
        cfg = get_config(arch).reduced()
        pshape = jax.eval_shape(
            functools.partial(M.init_params, cfg), jax.random.key(0)
        )
        pspec = SH.param_specs(cfg, mesh)
        # must zip without structure errors and cover every leaf
        leaves = jax.tree.leaves(
            jax.tree.map(lambda p, s: (p, s.shape), pspec, pshape,
                         is_leaf=lambda x: isinstance(x, P))
        )
        assert leaves
        up = SH.fsdp_upgrade(pspec, pshape, mesh)
        jax.tree.map(lambda p, s: None, up, pshape,
                     is_leaf=lambda x: isinstance(x, P))
