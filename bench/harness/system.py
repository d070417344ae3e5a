"""The system under test, built from a configuration file as the serving
entry point (``repro.launch.serve.run_batched``) builds it: a mesh made
global, ``BatchedSpecServer`` with the DyTC scheduler, the paged cache and
the configuration's server settings, under ``ServeLoop``.

Only this module imports the program (``src/repro``).
"""
from __future__ import annotations

import dataclasses
import sys

from harness.registry import REPO_DIR

_SIZE_KEYS = {                       # configuration file -> ModelConfig
    "num_hidden_layers": "num_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "head_dim": "head_dim",
    "rope_theta": "rope_theta",
    "norm_epsilon": "norm_eps",
    "hidden_act": "act",
    "mlp_gated": "mlp_gated",
    "tie_word_embeddings": "tie_embeddings",
    "torch_dtype": "dtype",
}


def import_program():
    src = str(REPO_DIR / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    import repro  # noqa: F401  (fails where the program is absent)


def model_config(c: dict):
    """The program's ``ModelConfig`` for a configuration file: the
    registered architecture with every size taken from the file."""
    import_program()
    from repro.config import get_config

    base = get_config(c["model"])
    return dataclasses.replace(
        base, **{dst: c[src] for src, dst in _SIZE_KEYS.items()})


def mesh(c: dict):
    import jax
    from repro.launch.mesh import mesh_from_spec

    m = mesh_from_spec(c["server"]["mesh"])
    jax.sharding.set_mesh(m)
    return m


def param_shardings(cfg, m):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.launch import sharding as SH

    return jax.tree.map(lambda s: NamedSharding(m, s),
                        SH.param_specs(cfg, m),
                        is_leaf=lambda x: isinstance(x, PartitionSpec))


def program_param_shapes(cfg):
    """Leaf shapes of the program's own init (to check the benchmark's
    weights against the layout the server expects)."""
    import jax
    from repro.models import model as M

    return jax.eval_shape(lambda k: M.init_params(cfg, k),
                          jax.random.PRNGKey(0))


def server(c: dict, cfg, params, m):
    from repro.core.dsia import build_hierarchy, layer_sparsity
    from repro.serving.server import BatchedSpecServer

    s = c["server"]
    kw = dict(max_batch=s["max_batch"], max_len=s["max_len"], mode=s["mode"],
              mesh=m, sync_every=s["sync_every"])
    if s["mode"] == "cascade_fused":
        kw["hierarchy"] = build_hierarchy(cfg, s["hierarchy"])
    else:
        kw["draft_spec"] = layer_sparsity(cfg, 0.4)
    if s["paged"]:
        kw.update(paged=True, page_size=s["page_size"])
        if s["prefill_chunk"]:
            kw["prefill_chunk"] = s["prefill_chunk"]
    return BatchedSpecServer(cfg, params, **kw)


def loop(srv, max_batch: int):
    from repro.serving.scheduler import RequestScheduler, ServeLoop

    sched = RequestScheduler(max_batch)
    return sched, ServeLoop(srv, sched)


def request(prompt, max_new_tokens: int):
    from repro.serving.scheduler import Request

    return Request(prompt=prompt, max_new_tokens=max_new_tokens)
