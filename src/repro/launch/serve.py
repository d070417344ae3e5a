"""Serving driver: CAS-Spec engine (single stream) or batched server.

  PYTHONPATH=src python -m repro.launch.serve --arch vicuna-7b --reduced \
      --scheduler dytc --tokens 64

``--mesh model=K,data=D`` switches to the batched continuous-batching
server (``serving.server.BatchedSpecServer`` + ``ServeLoop``) with the
target tensor-parallel over ``model`` and the batch slots data-parallel
over ``data`` — the single-dispatch round runs unchanged on the mesh (see
docs/sharding.md). Off-accelerator, force host devices first:

  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  PYTHONPATH=src python -m repro.launch.serve --arch vicuna-7b --reduced \
      --mesh model=2,data=4 --mode chain_fused --batch 4 --tokens 32

Observability (docs/observability.md): ``--metrics-port`` serves live
Prometheus text at ``/metrics`` while the run is in flight,
``--trace-out`` records Chrome-trace spans of the host-loop phases
(open in Perfetto), ``--profile-dir`` wraps the run in
``jax.profiler.trace``, and ``--metrics-jsonl`` appends the end-of-run
registry snapshot as one JSONL record. Regardless of flags, the LAST
stdout line is a single machine-readable JSON summary (``kind:
"serve_summary"``) sourced from the metrics registry.

Compiled programs persist across runs (``configure_compile_cache``): in
``$JAX_COMPILATION_CACHE_DIR`` when it is set, else in ``.jax_cache`` at
the root of the checkout.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import time
from pathlib import Path

import jax

from repro.config import get_config
from repro.core.cascade import (
    ARScheduler, HCScheduler, PLDScheduler, SDScheduler, TreeScheduler,
    VCHCScheduler, VCScheduler,
)
from repro.core.dsia import build_hierarchy, layer_sparsity
from repro.core.dytc import DyTCScheduler
from repro.core.engine import SpecEngine
from repro.data import SPEC_TASKS, make_task_prompts
from repro.models import model as M
from repro.serving.exporters import JsonlSink, MetricsHTTPServer
from repro.serving.telemetry import TraceRecorder, profiler_trace

SCHEDULERS = {
    "ar": lambda e, cfg: ARScheduler(e),
    "pld": lambda e, cfg: PLDScheduler(e, k=8),
    "swift": lambda e, cfg: SDScheduler(e, layer_sparsity(cfg, 0.4), k=4),
    "vc": lambda e, cfg: VCScheduler(e, layer_sparsity(cfg, 0.4)),
    "hc": lambda e, cfg: HCScheduler(e, layer_sparsity(cfg, 0.4)),
    "vchc": lambda e, cfg: VCHCScheduler(e, layer_sparsity(cfg, 0.4)),
    "tree": lambda e, cfg: TreeScheduler(e, layer_sparsity(cfg, 0.4)),
    "dytc": lambda e, cfg: DyTCScheduler(e, build_hierarchy(cfg)),
}


REPO_ROOT = Path(__file__).resolve().parents[3]


def configure_compile_cache() -> str:
    """Turn on JAX's persistent compile cache and return its directory.

    ``$JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and is
    left alone; otherwise the cache lives at the fixed ``<repo>/.jax_cache``
    (the directory is part of each entry's key, so it must not move)."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def init_params(cfg, seed: int = 0, mesh=None) -> dict:
    """Random weights from ``seed``, made on device by one jitted program.

    On a mesh every parameter is created directly in its tensor-parallel
    shard (``launch.sharding.param_specs`` as ``out_shardings``), so a model
    larger than one device's memory never exists whole on any device."""
    out_shardings = None
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec
        from repro.launch import sharding as SH

        out_shardings = jax.tree.map(
            lambda s: NamedSharding(mesh, s), SH.param_specs(cfg, mesh),
            is_leaf=lambda x: isinstance(x, PartitionSpec),
        )
    init = jax.jit(functools.partial(M.init_params, cfg),
                   out_shardings=out_shardings)
    return init(jax.random.PRNGKey(seed))


def _emit_summary(summary: dict, args) -> None:
    """The one machine-readable final line (+ optional JSONL record)."""
    if args.metrics_jsonl:
        with JsonlSink(args.metrics_jsonl) as sink:
            sink.write(summary)
    print(json.dumps(summary, sort_keys=True))


def run_batched(cfg, params, args, mesh):
    """``--mesh`` path: mesh-sharded batched serving rounds.

    ``mesh`` must be the process's global mesh (``jax.sharding.set_mesh``:
    this process owns serving end to end, which activates the
    engine-internal batch pins; libraries embedding the server pass
    ``mesh=`` only — see the server docstring). Returns the server, the
    finished requests and the summary record it printed."""
    from repro.serving.scheduler import Request, RequestScheduler, ServeLoop
    from repro.serving.server import BatchedSpecServer

    print(f"mesh: {dict(mesh.shape)} over {len(mesh.devices.flat)} devices")
    srv_kw: dict = {}
    if args.mode != "cascade_fused":
        srv_kw["draft_spec"] = layer_sparsity(cfg, 0.4)
    if args.temperature > 0.0:
        from repro.serving.sampler import SamplingParams

        srv_kw["sampling"] = SamplingParams(
            temperature=args.temperature, top_k=args.top_k,
            top_p=args.top_p, seed=args.seed,
        )
    if args.paged or args.prefill_chunk:
        # block-paged KV cache (+ optional in-round chunked prefill) —
        # token-identical to the dense path; see docs/paging.md
        srv_kw.update(paged=True, page_size=args.page_size)
        if args.prefill_chunk:
            srv_kw["prefill_chunk"] = args.prefill_chunk
    srv = BatchedSpecServer(
        cfg, params, max_batch=args.batch, max_len=1024,
        mode=args.mode, mesh=mesh, **srv_kw,
    )
    endpoint = (MetricsHTTPServer(srv.metrics, port=args.metrics_port)
                if args.metrics_port is not None else None)
    if endpoint is not None:
        print(f"metrics: {endpoint.url}")
    trace = TraceRecorder() if args.trace_out else None
    sched = RequestScheduler(args.batch)
    for p in make_task_prompts(SPEC_TASKS[args.task], args.batch, cfg.vocab_size):
        sched.submit(Request(prompt=p, max_new_tokens=args.tokens))
    loop = ServeLoop(srv, sched, trace=trace)
    t0 = time.perf_counter()
    with profiler_trace(args.profile_dir):
        while sched.busy:
            loop.step_once()
        srv.flush()
    dt = time.perf_counter() - t0
    tok = sum(len(r.generated) for r in sched.finished)
    print(f"mode={args.mode} mesh={args.mesh} requests={len(sched.finished)} "
          f"tokens={tok} time={dt:.2f}s ({dt/max(tok,1)*1e3:.1f} ms/tok)")
    if trace is not None:
        trace.save(args.trace_out)
        print(f"trace: {args.trace_out} (open in https://ui.perfetto.dev)")
    if endpoint is not None:
        endpoint.close()
    summary = {
        "kind": "serve_summary",
        "mesh": args.mesh,
        "requests": len(sched.finished),
        "delivered_tokens": tok,
        "wall_s": dt,
        **srv.metrics_summary(),
    }
    _emit_summary(summary, args)
    return srv, sched.finished, summary


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="vicuna-7b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--scheduler", default="dytc", choices=sorted(SCHEDULERS))
    ap.add_argument("--tokens", type=int, default=64)
    ap.add_argument("--task", default="summarization")
    ap.add_argument("--mesh", default=None,
                    help="'model=K,data=D' -> mesh-sharded batched server")
    ap.add_argument("--mode", default="chain_fused",
                    choices=["chain_fused", "legacy", "tree_fused",
                             "cascade_fused"],
                    help="batched server mode (with --mesh)")
    ap.add_argument("--batch", type=int, default=4,
                    help="batch slots (with --mesh)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (batched path; 0 = greedy, "
                         "the default — lossless stochastic verify when >0)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="top-k filter for sampled serving (0 = off)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus mass for sampled serving (1.0 = off)")
    ap.add_argument("--seed", type=int, default=None,
                    help="base PRNG seed for sampled serving (per-request "
                         "streams derive from it and the admission order)")
    ap.add_argument("--paged", action="store_true",
                    help="block-paged KV cache (batched path; lossless — "
                         "see docs/paging.md)")
    ap.add_argument("--page-size", type=int, default=64,
                    help="tokens per KV page (with --paged)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help=">0: non-blocking admission — prompts prefill "
                         "inside the fused rounds, this many tokens per "
                         "round (implies --paged; single-round modes only)")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve Prometheus /metrics on this port (0 = "
                         "ephemeral; batched path)")
    ap.add_argument("--trace-out", default=None,
                    help="write Chrome trace-event JSON of the host-loop "
                         "phases here (batched path)")
    ap.add_argument("--profile-dir", default=None,
                    help="wrap the run in jax.profiler.trace(log_dir)")
    ap.add_argument("--metrics-jsonl", default=None,
                    help="append the final summary record to this JSONL file")
    return ap.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    configure_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = dataclasses.replace(cfg.reduced(), num_layers=8)
    if args.mesh:
        from repro.launch.mesh import mesh_from_spec

        mesh = mesh_from_spec(args.mesh)
        jax.sharding.set_mesh(mesh)
        params = init_params(cfg, 0, mesh)
        run_batched(cfg, params, args, mesh)
        return
    params = init_params(cfg, 0)
    prompt = make_task_prompts(SPEC_TASKS[args.task], 1, cfg.vocab_size)[0]

    eng = SpecEngine(cfg, params, max_len=1024)
    eng.start(prompt)
    sched = SCHEDULERS[args.scheduler](eng, cfg)
    t0 = time.perf_counter()
    with profiler_trace(args.profile_dir):
        out = sched.generate(args.tokens)
    dt = time.perf_counter() - t0
    s = eng.stats
    print(f"scheduler={args.scheduler} tokens={len(out)} time={dt:.2f}s "
          f"({dt/len(out)*1e3:.1f} ms/tok)")
    print("output:", out[:32], "..." if len(out) > 32 else "")
    summary = {
        "kind": "serve_summary",
        "scheduler": args.scheduler,
        "delivered_tokens": len(out),
        "wall_s": dt,
        "rounds": s["rounds"],
        "target_calls": s["target_calls"],
        "mean_accepted": s["accepted_tokens"] / max(s["rounds"], 1),
    }
    _emit_summary(summary, args)


if __name__ == "__main__":
    main()
