"""The Pallas kernels under ``shard_map`` on a mesh: same numbers as off-mesh.

A Mosaic kernel cannot be split by the SPMD partitioner, so on a mesh the
tree-verify pass and the W8A8 MLP matmuls run per shard (KV heads / weight
columns / the contraction dim over ``model``, batch over ``data``). Here
they run in interpret mode on forced host devices and must reproduce the
single-device result: the tree pass exactly, the W8A8 MLP (on int8 images
quantized over the whole K before sharding) up to the order of the f32 sum
of its contraction-split partials.

Runs in a SUBPROCESS: the forced device count must be set before jax
initializes, and the rest of the suite must keep seeing one device.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
MESHES = ("model=4", "model=2,data=2")

SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import functools, json
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import mesh_from_spec
    from repro.models.attention import decode_attention
    from repro.kernels.int8_matmul import quantize_weight
    from repro.models.layers import mlp_apply, mlp_init

    rng = np.random.default_rng(0)
    p = {k: quantize_weight(w) for k, w in
         mlp_init(jax.random.PRNGKey(0), 256, 512, True, jnp.float32).items()}
    x = jnp.asarray(rng.normal(size=(4, 6, 256)), jnp.float32)
    mlp = jax.jit(functools.partial(mlp_apply, act="silu", gated=True,
                                    quantize="int8"))

    B, T, H, KV, hd, S = 4, 8, 8, 4, 64, 32
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    q = jax.random.normal(ks[0], (B, T, H, hd))
    kc = jax.random.normal(ks[1], (B, S, KV, hd))
    vc = jax.random.normal(ks[2], (B, S, KV, hd))
    kn = jax.random.normal(ks[3], (B, T, KV, hd))
    vn = jax.random.normal(ks[4], (B, T, KV, hd))
    pos = jnp.asarray([5, 17, 32, 0], jnp.int32)
    qp = pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None]
    parents = rng.integers(0, np.arange(T) + 1) - 1          # a random tree
    anc = np.eye(T, dtype=bool)
    for t in range(1, T):
        anc[t] |= anc[max(parents[t], 0)] if parents[t] >= 0 else False
    tm = jnp.asarray(np.broadcast_to(anc, (B, T, T)))
    tree = jax.jit(functools.partial(decode_attention, tree_mask=tm,
                                     backend="pallas"))
    args = (q, kc, vc, pos, kn, vn, qp)

    ref_mlp, ref_tree = np.asarray(mlp(p, x)), np.asarray(tree(*args))
    out = {}
    for spec in %r:
        with jax.sharding.set_mesh(mesh_from_spec(spec)):
            got_mlp, got_tree = np.asarray(mlp(p, x)), np.asarray(tree(*args))
        out[spec] = {
            "mlp_rel": float(np.max(np.abs(got_mlp - ref_mlp))
                             / np.max(np.abs(ref_mlp))),
            "tree_equal": bool(np.array_equal(got_tree, ref_tree)),
        }
    print(json.dumps(out))
    """
    % (MESHES,)
)


@pytest.fixture(scope="module")
def results():
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT], capture_output=True, text=True,
        env=env, timeout=540,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("mesh", MESHES)
def test_tree_kernel_on_mesh_matches_single_device(results, mesh):
    assert results[mesh]["tree_equal"]


@pytest.mark.parametrize("mesh", MESHES)
def test_int8_mlp_on_mesh_matches_single_device(results, mesh):
    # whole-K scales make every shard quantize exactly like one device;
    # only the f32 sum of the contraction-split partials is reordered
    assert results[mesh]["mlp_rel"] < 1e-5
