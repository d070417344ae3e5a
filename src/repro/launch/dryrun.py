"""Multi-pod dry-run: lower + compile every (arch x shape) on the production
mesh, print memory/cost analysis, dump roofline JSON.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch mixtral-8x22b --shape decode_32k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] --out results/
"""
# The dry-run (and ONLY the dry-run) needs 512 placeholder devices — set
# BEFORE any other import; jax locks the device count on first init.
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

import argparse
import functools
import json
import sys
import time
import traceback
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis.roofline import analyze_compiled
from repro.config import INPUT_SHAPES, get_config, get_shape
from repro.configs import ASSIGNED_ARCHS
from repro.launch import sharding as SH
from repro.launch.mesh import make_production_mesh
from repro.models import model as M
from repro.training.optimizer import adamw_init
from repro.training.train_step import make_train_step

DRAFT_T = 8          # tree bucket lowered for serve_step (the paper's verify)


# ------------------------------------------------------------- input specs
def input_specs(cfg, shape, kind: str) -> dict:
    """ShapeDtypeStruct stand-ins for every model input — weak-type correct,
    shardable, no device allocation."""
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    tok_shape = (B, S, cfg.num_codebooks) if cfg.num_codebooks else (B, S)
    if kind == "decode":
        T = DRAFT_T
        tok_shape = (B, T, cfg.num_codebooks) if cfg.num_codebooks else (B, T)
    out = {"tokens": jax.ShapeDtypeStruct(tok_shape, i32)}
    if cfg.num_image_tokens and kind in ("train", "prefill"):
        Ti = min(cfg.num_image_tokens, S)
        out["image_embeds"] = jax.ShapeDtypeStruct(
            (B, Ti, cfg.d_model), jnp.dtype(cfg.dtype)
        )
        out["image_mask"] = jax.ShapeDtypeStruct((B, S), i32)
    return out


def _shardings(mesh, tree):
    return jax.tree.map(
        lambda p: NamedSharding(mesh, p), tree, is_leaf=lambda x: isinstance(x, P)
    )


def _local_bytes(shape_tree, spec_tree, mesh) -> float:
    """Per-device bytes of a sharded pytree (leaf bytes / sharded mesh axes)."""
    total = 0.0

    def add(shape, spec):
        nonlocal total
        n = float(np.prod(shape.shape)) * shape.dtype.itemsize
        div = 1
        for ax in spec:
            if ax is None:
                continue
            for a in (ax,) if isinstance(ax, str) else ax:
                div *= mesh.shape[a]
        total += n / div

    jax.tree.map(
        lambda sp, sh: add(sh, sp), spec_tree, shape_tree,
        is_leaf=lambda x: isinstance(x, P),
    )
    return total


def _analytic_traffic(kind: str, params_local: float, cache_local: float,
                      act_local: float) -> float:
    """Minimum HBM traffic per device per step (the roofline memory term).

    decode : weights read once + cache read once (writes are T/S, negligible)
    prefill: weights read once + cache written once + activation stream
    train  : weights read 2x (fwd + remat recompute), grads written once,
             f32 moments read+written (16B per 2B bf16 param -> 8x),
             activation stream 3x (fwd, recompute, bwd)
    """
    if kind == "decode":
        return params_local + cache_local + act_local
    if kind == "prefill":
        return params_local + cache_local + act_local
    return params_local * (2 + 1 + 8) + act_local * 3


def params_shapes(cfg):
    return jax.eval_shape(functools.partial(M.init_params, cfg), jax.random.key(0))


# ----------------------------------------------------------------- builders
def _inference_fsdp(cfg) -> bool:
    """TP-only weight shard too big for one chip's HBM -> 2D-shard weights."""
    return cfg.param_count() * 2 / 16 > 10e9


def build_train(cfg, shape, mesh):
    pshape = params_shapes(cfg)
    # training always FSDP-shards weights+moments (4x f32 moments)
    pspec = SH.fsdp_upgrade(SH.param_specs(cfg, mesh), pshape, mesh)
    ospec = SH.opt_specs(pspec)
    bspec = SH.batch_specs(cfg, mesh, global_batch=shape.global_batch)
    oshape = jax.eval_shape(adamw_init, pshape)
    batch = input_specs(cfg, shape, "train")
    step = make_train_step(cfg, remat=True)
    in_sh = (_shardings(mesh, pspec), _shardings(mesh, ospec),
             {k: _shardings(mesh, bspec[k]) for k in batch})
    out_sh = (in_sh[0], in_sh[1],
              jax.tree.map(lambda _: NamedSharding(mesh, P()),
                           {"ce": 0, "moe_aux": 0, "loss": 0, "lr": 0, "grad_norm": 0}))
    fn = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh,
                 donate_argnums=(0, 1))
    dp_total = int(np.prod([mesh.shape[a] for a in mesh.axis_names if a != "model"]))
    params_local = _local_bytes(pshape, pspec, mesh)
    act_local = (cfg.num_layers * shape.global_batch * shape.seq_len
                 * cfg.d_model * 2 * 6) / dp_total
    traffic = _analytic_traffic("train", params_local, 0.0, act_local)
    return fn, (pshape, oshape, batch), traffic


def build_prefill(cfg, shape, mesh):
    pshape = params_shapes(cfg)
    pspec = SH.param_specs(cfg, mesh)
    if _inference_fsdp(cfg):
        pspec = SH.fsdp_upgrade(pspec, pshape, mesh)
    cspec = SH.cache_specs(cfg, mesh)
    bspec = SH.batch_specs(cfg, mesh, global_batch=shape.global_batch)
    cshape = jax.eval_shape(
        functools.partial(
            M.init_cache, cfg, shape.global_batch, shape.seq_len,
            dtype=jnp.dtype(cfg.dtype),
        )
    )
    batch = input_specs(cfg, shape, "prefill")
    dp = SH._dp(mesh)
    logits_spec = (
        P(dp, None, "model") if cfg.num_codebooks else P(dp, "model")
    )

    def fn(params, batch_, cache):
        return M.prefill(cfg, params, batch_, cache)

    in_sh = (_shardings(mesh, pspec),
             {k: _shardings(mesh, bspec[k]) for k in batch},
             _shardings(mesh, cspec))
    out_sh = (NamedSharding(mesh, logits_spec), _shardings(mesh, cspec))
    jfn = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                  donate_argnums=(2,))
    dp_total = int(np.prod([mesh.shape[a] for a in mesh.axis_names if a != "model"]))
    params_local = _local_bytes(pshape, pspec, mesh)
    cache_local = _local_bytes(cshape, cspec, mesh)
    act_local = (cfg.num_layers * shape.global_batch * shape.seq_len
                 * cfg.d_model * 2 * 4) / dp_total
    traffic = _analytic_traffic("prefill", params_local, cache_local, act_local)
    return jfn, (pshape, batch, cshape), traffic


def build_serve(cfg, shape, mesh):
    """CAS-Spec verify step: tree-decode DRAFT_T staged tokens + commit the
    accepted path — the paper's technique as the lowered decode step."""
    long_ctx = shape.seq_len > 100_000
    shard_seq = long_ctx and shape.global_batch == 1
    pshape = params_shapes(cfg)
    pspec = SH.param_specs(cfg, mesh)
    if _inference_fsdp(cfg):
        pspec = SH.fsdp_upgrade(pspec, pshape, mesh)
    cspec = SH.cache_specs(cfg, mesh, shard_seq=shard_seq, ring_window=long_ctx)
    stspec = SH.staged_specs(cfg, mesh, shard_seq=shard_seq)
    cshape = jax.eval_shape(
        functools.partial(
            M.init_cache, cfg, shape.global_batch, shape.seq_len,
            ring_window=long_ctx, dtype=jnp.dtype(cfg.dtype),
        )
    )
    B = shape.global_batch
    T = DRAFT_T
    toks = input_specs(cfg, shape, "decode")["tokens"]
    tmask = jax.ShapeDtypeStruct((T, T), jnp.bool_)
    path = jax.ShapeDtypeStruct((B, T), jnp.int32)
    nacc = jax.ShapeDtypeStruct((B,), jnp.int32)
    dp = SH._dp(mesh)
    bax = dp if B >= 16 else None

    # context-parallel cache partials: axes carrying the cache seq dim
    # (see sharding.cache_seq_axes + attention.decode_attention)
    seq_axes = SH.cache_seq_axes(cfg, mesh, shard_seq=shard_seq)

    def serve_step(params, cache, tokens, tree_mask, path_idx, n_acc):
        logits, staged = M.decode_step(
            cfg, params, cache, tokens, tree_mask=tree_mask, seq_axes=seq_axes
        )
        new_cache = M.commit_cache(cfg, cache, staged, path_idx, n_acc)
        return jnp.argmax(logits, axis=-1), new_cache

    in_sh = (
        _shardings(mesh, pspec),
        _shardings(mesh, cspec),
        NamedSharding(mesh, P(bax, None, None) if cfg.num_codebooks else P(bax, None)),
        NamedSharding(mesh, P()),
        NamedSharding(mesh, P(bax, None)),
        NamedSharding(mesh, P(bax)),
    )
    out_sh = (
        NamedSharding(mesh, P(bax, None, None) if cfg.num_codebooks else P(bax, None)),
        _shardings(mesh, cspec),
    )
    jfn = jax.jit(serve_step, in_shardings=in_sh, out_shardings=out_sh,
                  donate_argnums=(1,))
    params_local = _local_bytes(pshape, pspec, mesh)
    cache_local = _local_bytes(cshape, cspec, mesh)
    traffic = _analytic_traffic("decode", params_local, cache_local, 0.0)
    return jfn, (pshape, cshape, toks, tmask, path, nacc), traffic


BUILDERS = {"train": build_train, "prefill": build_prefill, "decode": build_serve}


def applicable(cfg, shape) -> bool:
    if shape.seq_len > 100_000:
        return cfg.supports_long_context
    return True


# ----------------------------------------------------------------- runner
def run_one(arch: str, shape_name: str, *, multi_pod: bool, out_dir: Optional[str] = None,
            verbose: bool = True) -> dict:
    import dataclasses

    cfg = get_config(arch)
    if cfg.moe is not None:
        # TPU execution knobs: sharded expert-group dispatch (see models.moe)
        cfg = dataclasses.replace(
            cfg,
            moe=dataclasses.replace(
                cfg.moe, exec_groups=32, prefill_dropless=False
            ),
        )
    shape = get_shape(shape_name)
    if not applicable(cfg, shape):
        return {"arch": arch, "shape": shape_name, "status": "skipped",
                "reason": "full-attention arch at 500k (see DESIGN.md)"}
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.perf_counter()
    # set_mesh (not `with mesh:`) so with_sharding_constraint sees the
    # abstract mesh during tracing (models.shard_utils.constrain).
    jax.sharding.set_mesh(mesh)
    fn, args, traffic = BUILDERS[shape.kind](cfg, shape, mesh)
    lowered = fn.lower(*args)
    compiled = lowered.compile()
    dt = time.perf_counter() - t0
    name = f"{arch}/{shape_name}/{'2pod' if multi_pod else '1pod'}"
    rep = analyze_compiled(name, compiled, analytic_bytes=traffic)
    mem = compiled.memory_analysis()
    result = {
        "arch": arch, "shape": shape_name, "status": "ok",
        "mesh": "2x16x16" if multi_pod else "16x16",
        "kind": shape.kind,
        "compile_s": round(dt, 1),
        "memory_analysis": {
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "alias_bytes": int(mem.alias_size_in_bytes),
        },
        "roofline": rep.to_dict(),
    }
    if verbose:
        ma = result["memory_analysis"]
        print(f"== {name} kind={shape.kind} compile={dt:.1f}s")
        print(f"   memory/device: args={ma['argument_bytes']/2**30:.2f}GiB "
              f"temp={ma['temp_bytes']/2**30:.2f}GiB aliased={ma['alias_bytes']/2**30:.2f}GiB")
        print(f"   flops/device={rep.flops:.3e} bytes/device={rep.bytes_hbm:.3e} "
              f"coll={rep.coll_total:.3e}")
        print(f"   t_comp={rep.t_compute*1e3:.3f}ms t_mem={rep.t_memory*1e3:.3f}ms "
              f"t_coll={rep.t_collective*1e3:.3f}ms -> {rep.bottleneck}-bound")
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        fname = f"{arch}__{shape_name}__{'2pod' if multi_pod else '1pod'}.json"
        with open(os.path.join(out_dir, fname), "w") as f:
            json.dump(result, f, indent=1)
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args()

    pairs = []
    if args.all:
        for a in ASSIGNED_ARCHS:
            for s in INPUT_SHAPES:
                pairs.append((a, s))
    else:
        pairs = [(args.arch, args.shape)]

    failures = 0
    for arch, shp in pairs:
        try:
            r = run_one(arch, shp, multi_pod=args.multi_pod, out_dir=args.out)
            if r["status"] == "skipped":
                print(f"== {arch}/{shp}: SKIP ({r['reason']})")
        except Exception as e:
            failures += 1
            print(f"== {arch}/{shp}: FAILED: {e}")
            traceback.print_exc()
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
