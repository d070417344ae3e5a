#!/usr/bin/env python3
"""Bring-up smoke of the batched CAS-Spec server on a TPU.

    python3 chip_smoke.py               # one chip: starcoder2-3b, 30 layers
    python3 chip_smoke.py --four-chips  # 2x2 host: internlm2-20b on model=4
    JAX_PLATFORMS=cpu python3 chip_smoke.py --size tiny   # CPU rehearsal

Drives the production serving path through its own entry points
(``repro.launch.serve.init_params`` / ``run_batched``: ``BatchedSpecServer``
under ``ServeLoop``) at the model's published widths and full depth, with
random bf16 weights and task prompts made from ``--seed``. One chip runs
``chain_fused`` and ``tree_fused`` (block-paged, chunked prefill),
``cascade_fused`` and one sampled ``chain_fused`` run; ``--four-chips`` runs
only ``chain_fused`` and ``tree_fused`` on the tensor-parallel mesh.

Every greedy request is compared with a speculation-off decode of the same
weights (``ARScheduler`` over ``SpecEngine``). Rule: bf16 arithmetic makes
the served verify and the reference disagree in the low bits of the
logits, which flips the argmax wherever the reference's top two logits
(nearly) tie. A divergence is therefore accepted as rounding when the
served token is the reference's runner-up AND its logit is within
``NEAR_TIE`` standard deviations of the reference's top logit; any other
divergence fails the run. Tokens after the first divergence follow a
different context and are not compared. Unfinished requests, wrong token
counts and out-of-vocabulary tokens fail the run too.

Everything runs in this one process (a chip belongs to one process). The
script fails, printing no result, when JAX finds no TPU (except for the
``--size tiny`` rehearsal) or when the repository is not beside it. Its
last stdout line is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import gc
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

# logits of these random-weight models have unit scale; bf16 keeps 8 bits
# of mantissa, and a 30-48 layer stack perturbs a logit by a few 1e-2 of
# its spread — a tenth of the spread is the widest gap rounding can close
NEAR_TIE = 0.1

ONE_CHIP = {"arch": "starcoder2-3b", "mesh": "model=1,data=1",
            "requests": 8, "tokens": 100}
FOUR_CHIPS = {"arch": "internlm2-20b", "mesh": "model=4,data=1",
              "requests": 4, "tokens": 48}
PREFILL_CHUNK = 64      # prompt tokens a slot prefills per round (paged runs)


class SmokeFailure(Exception):
    pass


def log(msg: str) -> None:
    print(msg, flush=True)


def _runs(four_chips: bool) -> list:
    """(label, serve.py flags) per phase."""
    paged = ["--paged", "--prefill-chunk", str(PREFILL_CHUNK)]
    runs = [("chain_fused", ["--mode", "chain_fused"] + paged),
            ("tree_fused", ["--mode", "tree_fused"] + paged)]
    if not four_chips:
        runs += [("cascade_fused", ["--mode", "cascade_fused"]),
                 ("chain_fused_sampled",
                  ["--mode", "chain_fused", "--temperature", "0.8",
                   "--top-p", "0.95"] + paged)]
    return runs


class CompileClock:
    """Seconds JAX spent compiling (or loading compiled programs)."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event in self.EVENTS:
            self.total += duration


def _check_requests(label: str, finished: list, n_req: int, n_tok: int,
                    vocab: int) -> None:
    if len(finished) != n_req:
        raise SmokeFailure(f"{label}: {len(finished)}/{n_req} requests finished")
    for r in finished:
        if not r.done or len(r.generated) != n_tok:
            raise SmokeFailure(
                f"{label}: request {r.request_id} returned "
                f"{len(r.generated)} of {n_tok} tokens")
        bad = [t for t in r.generated if not 0 <= t < vocab]
        if bad:
            raise SmokeFailure(f"{label}: out-of-vocabulary tokens {bad[:5]}")


def _check_kernels(srv, label: str) -> None:
    """The compiled round programs must call the Pallas kernels."""
    for name, (fn, ex_args) in srv.round_executables().items():
        t0 = time.perf_counter()
        text = fn.lower(*ex_args).compile().as_text()
        n = text.count("tpu_custom_call")
        log(f"  {label}/{name}: {n} tpu_custom_call "
            f"(lower+compile {time.perf_counter() - t0:.1f}s)")
        if name in ("tree_verify", "round", "rescore_verify",
                    "cascade_draft") and n == 0:
            raise SmokeFailure(f"{label}/{name}: no Pallas kernel compiled in")


class Reference:
    """Speculation-off greedy decode of the same weights (one engine)."""

    def __init__(self, cfg, params, max_len: int):
        from repro.core.engine import SpecEngine

        self.cfg, self.params, self.max_len = cfg, params, max_len
        self.eng = SpecEngine(cfg, params, max_len=max_len)
        self._prefill = None

    def generate(self, prompt, n: int) -> list:
        from repro.core.cascade import ARScheduler

        self.eng.start(prompt)
        return list(ARScheduler(self.eng).generate(n)) if n else []

    def logits_before(self, prompt, ref: list, d: int):
        """f64 logits the reference chose ``ref[d]`` from, over the real
        vocabulary (padded ids carry a -1e30 mask)."""
        if d == 0:
            from repro.models import model as M

            if self._prefill is None:
                self._prefill = jax.jit(functools.partial(M.prefill, self.cfg))
            cache = M.init_cache(self.cfg, 1, self.max_len,
                                 dtype=jnp.dtype(self.cfg.dtype))
            lg, _ = self._prefill(
                self.params, {"tokens": jnp.asarray(prompt[None])}, cache)
        else:
            self.generate(prompt, d - 1)
            lg = self.eng.draft_logits(
                "full", np.asarray([self.eng.pending], np.int32),
                np.zeros(1, np.int32), np.ones((1, 1), bool))
        return np.asarray(lg, np.float64)[0, : self.cfg.vocab_size]


def _compare(ref_engine: Reference, label: str, finished: list,
             refs: dict) -> dict:
    """Share of tokens matching the reference up to the first divergence;
    raises on a divergence that is not a near-tie (module docstring)."""
    matched = total = 0
    worst = None
    for r in finished:
        key = r.prompt.tobytes()
        ref, got = refs[key], list(r.generated)
        d = next((i for i, (a, b) in enumerate(zip(got, ref)) if a != b), None)
        total += len(ref)
        if d is None:
            matched += len(ref)
            continue
        matched += d
        lg = ref_engine.logits_before(r.prompt, ref, d)
        if int(np.argmax(lg)) != ref[d]:
            raise SmokeFailure(f"{label}: reference replay is not deterministic")
        order = np.argsort(-lg)
        rank = int(np.nonzero(order == got[d])[0][0]) + 1
        gap = float(lg[ref[d]] - lg[got[d]]) / float(np.std(lg))
        log(f"  {label}: request {r.request_id} first diverges at token {d}: "
            f"served {got[d]} (reference rank {rank}), reference {ref[d]}, "
            f"gap {gap:.4f} std")
        if rank != 2 or gap > NEAR_TIE:
            raise SmokeFailure(
                f"{label}: divergence at token {d} is not a near-tie "
                f"(rank {rank}, gap {gap:.4f} std > {NEAR_TIE})")
        worst = gap if worst is None else max(worst, gap)
    return {"match_share": matched / max(total, 1), "worst_tie_gap": worst}


def run(args) -> dict:
    from repro.config import get_config
    from repro.launch import serve
    from repro.launch.mesh import mesh_from_spec

    dev = jax.devices()[0]
    if dev.platform != "tpu" and args.size != "tiny":
        raise SmokeFailure(
            f"JAX found no TPU (platform {dev.platform!r}); this smoke "
            "measures the chip and never falls back")
    on_tpu = dev.platform == "tpu"
    plan = FOUR_CHIPS if args.four_chips else ONE_CHIP
    n_req, n_tok = plan["requests"], plan["tokens"]
    log(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}")
    log(f"compile cache: {serve.configure_compile_cache()}")

    cfg = get_config(plan["arch"])
    if args.size == "tiny":
        # a vocabulary off the 256 multiple keeps the masked padded-logit
        # tail internlm2-20b (92544 ids) has
        cfg = dataclasses.replace(cfg.reduced(), dtype="bfloat16",
                                  vocab_size=500)
    mesh = mesh_from_spec(plan["mesh"])
    jax.sharding.set_mesh(mesh)
    clock = CompileClock()

    t0 = time.perf_counter()
    params = jax.block_until_ready(serve.init_params(cfg, args.seed, mesh))
    leaves = jax.tree.leaves(params)
    on_dev0 = sum(s.data.nbytes for a in leaves for s in a.addressable_shards
                  if s.device == dev)
    log(f"model: {cfg.name} layers={cfg.num_layers} d_model={cfg.d_model} "
        f"heads={cfg.num_heads}/{cfg.num_kv_heads} "
        f"params={sum(a.size for a in leaves)} dtype={cfg.dtype} "
        f"mesh={plan['mesh']} init={time.perf_counter() - t0:.1f}s "
        f"param_bytes={sum(a.nbytes for a in leaves)} on_device0={on_dev0}")

    outputs = {}
    for label, flags in _runs(args.four_chips):
        argv = ["--arch", plan["arch"], "--mesh", plan["mesh"],
                "--batch", str(n_req), "--tokens", str(n_tok),
                "--seed", str(args.seed)] + flags
        c0 = clock.total
        t0 = time.perf_counter()
        srv, finished, summary = serve.run_batched(
            cfg, params, serve.parse_args(argv), mesh)
        wall = time.perf_counter() - t0
        compile_s = clock.total - c0
        _check_requests(label, finished, n_req, n_tok, cfg.vocab_size)
        toks = sum(len(r.generated) for r in finished)
        log(f"[{label}] wall={wall:.2f}s compile={compile_s:.2f}s "
            f"tokens={toks} rounds={summary['rounds']} "
            f"tokens/round={summary['tokens_per_step']:.3f} "
            f"accept_rate={summary['spec_accept_rate']}")
        if on_tpu and srv.mode in ("tree_fused", "cascade_fused"):
            _check_kernels(srv, label)
        if "--temperature" not in flags:
            outputs[label] = finished
        del srv
        gc.collect()        # the server's jitted closures reference it

    t0 = time.perf_counter()
    ref_engine = Reference(cfg, params, max_len=1024)
    prompts = {r.prompt.tobytes(): r.prompt
               for r in next(iter(outputs.values()))}
    refs = {k: ref_engine.generate(p, n_tok) for k, p in prompts.items()}
    log(f"[reference] speculation-off decode: {len(refs)} requests x "
        f"{n_tok} tokens in {time.perf_counter() - t0:.2f}s")
    for label, finished in outputs.items():
        res = _compare(ref_engine, label, finished, refs)
        log(f"[{label}] matches reference: share={res['match_share']:.4f} "
            f"worst_near_tie_gap={res['worst_tie_gap']}")

    stats = dev.memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        log(f"peak_bytes_in_use={stats['peak_bytes_in_use']} "
            f"(device 0 of {len(jax.devices())})")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="internlm2-20b tensor-parallel over 4 chips")
    ap.add_argument("--size", choices=("full", "tiny"), default="full",
                    help="tiny: reduced widths for a CPU rehearsal")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and of sampling")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print("chip_smoke: src/repro is not beside this script; run it from "
              "a checkout of the repository", file=sys.stderr)
        return 2
    if args.size == "tiny" and args.four_chips:
        # the CPU rehearsal of the mesh path needs 4 host devices, which
        # must be requested before JAX initializes
        os.environ.setdefault(
            "XLA_FLAGS", "--xla_force_host_platform_device_count=4")
    sys.path.insert(0, SRC)
    try:
        device = run(args)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
