"""The serving entry point (``repro.launch.serve``) driven in-process.

Runs in a SUBPROCESS: it forces 4 host devices before jax initializes, and
``main`` sets the process-global mesh and compile cache, neither of which
may leak into the rest of the suite. Checks:

  - ``init_params`` on a mesh creates every parameter in its
    tensor-parallel shard (``out_shardings``), with the same values as the
    unsharded initializer;
  - ``main(argv)`` serves a reduced model end to end on ``model=2,data=2``
    and ends with its machine-readable summary line;
  - ``configure_compile_cache`` follows ``$JAX_COMPILATION_CACHE_DIR``
    and otherwise picks the fixed ``<repo>/.jax_cache``.
"""
import json
import os
import subprocess
import sys
import textwrap

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import contextlib, io, json
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.config import get_config
    from repro.launch import serve
    from repro.launch import sharding as SH
    from repro.launch.mesh import mesh_from_spec
    from repro.models import model as M

    out = {}
    cfg = get_config("internlm2-20b").reduced()
    mesh = mesh_from_spec("model=4")
    params = serve.init_params(cfg, 3, mesh)
    specs = SH.param_specs(cfg, mesh)
    want = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, PartitionSpec))
    out["placed"] = all(jax.tree.leaves(jax.tree.map(
        lambda a, s: a.sharding.is_equivalent_to(s, a.ndim), params, want)))
    dev0 = jax.devices()[0]
    out["bytes_total"] = sum(a.nbytes for a in jax.tree.leaves(params))
    out["bytes_dev0"] = sum(
        s.data.nbytes for a in jax.tree.leaves(params)
        for s in a.addressable_shards if s.device == dev0)
    ref = M.init_params(cfg, jax.random.PRNGKey(3))
    # jit may fold an init scale differently from eager: 1 f32 ulp apart
    out["same_values"] = all(jax.tree.leaves(jax.tree.map(
        lambda a, b: bool(np.allclose(np.asarray(a), np.asarray(b),
                                      rtol=2.0 ** -22, atol=0.0)),
        params, ref)))

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        serve.main(["--arch", "vicuna-7b", "--reduced", "--mesh",
                    "model=2,data=2", "--mode", "chain_fused", "--batch", "2",
                    "--tokens", "6"])
    out["summary"] = json.loads(buf.getvalue().strip().splitlines()[-1])
    out["cache_env"] = jax.config.jax_compilation_cache_dir
    os.environ.pop("JAX_COMPILATION_CACHE_DIR")
    out["cache_default"] = serve.configure_compile_cache()
    out["repo_root"] = str(serve.REPO_ROOT)
    print(json.dumps(out))
    """
)


def test_serve_entry_point_in_process(tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT], capture_output=True, text=True,
        env=env, timeout=540,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["placed"], "a parameter left init outside its TP sharding"
    assert res["same_values"], "sharded init changed the weights"
    # the model axis really splits the weights: device 0 holds well under
    # the whole model (embeddings, attention and MLP are all TP-sharded)
    assert res["bytes_dev0"] < 0.5 * res["bytes_total"]
    s = res["summary"]
    assert s["kind"] == "serve_summary" and s["requests"] == 2
    assert s["delivered_tokens"] == 12
    assert res["cache_env"] == str(tmp_path / "cache")
    assert res["cache_default"] == os.path.join(res["repo_root"], ".jax_cache")
