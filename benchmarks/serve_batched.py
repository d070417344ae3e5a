"""Batched serving A/B: cascade vs tree vs chain drafting, fused vs seed.

Three questions, one request stream:

  1. dispatch honesty (PR 1): fused one-dispatch chain drafting vs the
     seed's per-step loop — identical greedy outputs, fewer host syncs;
  2. tree economics (DyTC §4.2): batched on-device tree drafting
     (``tree_fused``) vs chain drafting — the paper's +47%/+48%
     tree-over-chain gains show up here as accepted tokens/step, which must
     be >= the chain path on the synthetic workload (trees hedge the
     target's choice with top-K siblings, so a round survives a wrong
     top-1). Round wall-clock is reported alongside: on CPU the tree's
     bigger verify block costs latency that the TPU's MXU absorbs.
  3. cascade economics (§4.1 + Alg. 1): the multi-level ``cascade_fused``
     mode (cheapest DSIA level drafts, stronger level rescores, target
     verifies) vs the single-level ``tree_fused`` arm — the namesake
     hierarchy must accept at least as many tokens/step as one-level
     drafting on the same stream (``serve/cascade_vs_tree``; the smoke
     canary fails below 0.9).

  4. draft-KV economics (staged-KV carry): tree drafting at the N=32
     bucket with ``draft_kv="carry"`` (each expansion decodes only the
     <= top_k appended tokens against carried staged KV) vs
     ``"recompute"`` (each expansion re-decodes the 32-wide padded block)
     — identical tokens/step by the parity contract
     (``serve/carry_vs_recompute_n32``; the smoke canary fails outside
     0.97–1.03), rounds/s reported as the speed story.

  5. round-pipeline economics (single-dispatch rounds): one fused
     device-resident dispatch per round with ``sync_every`` pipelining
     (``round_mode="single"``) vs the split draft+verify structure with
     per-round host syncs (``serve/round_single_vs_split``: rounds/s plus
     a host-vs-device per-round time breakdown), measured in the
     STEADY-STATE host-gated regime — adaptive routing under an
     unmeetable t_min stops neural drafting on both paths, leaving the
     per-round PLD retrieval / routing / sync overhead that the fused
     round moves on device (deterministic same-regime A/B, independent
     of per-machine cost coefficients). Alongside: the donated vs
     non-donated cache tps parity (``serve/donate_tps_parity``; the smoke
     canary fails outside 0.999–1.001 — donation is pure aliasing and
     must never change tokens).

  6. mesh-sharded round parity (docs/sharding.md): the same single-
     dispatch chain round on a forced 8-device host mesh (``model=2,
     data=4``) vs the single-device server — tokens/step must match
     EXACTLY (sharding is placement, never sampling; the smoke canary
     fails outside 0.999–1.001) with rounds/s reported as the
     communication-overhead story (``serve/sharded_vs_single``; smoke
     only, in a subprocess because the forced device count must precede
     jax initialization).

  7. telemetry economics (docs/observability.md): the device-carried
     round-telemetry buffer rides the single-dispatch round, so enabling
     it must add ZERO round dispatches and ZERO host syncs (exact
     equality; the runtime twin of the static
     ``assert_telemetry_transparent`` contract) and keep rounds/s within
     5% of the disabled server (``serve/telemetry_overhead``; the smoke
     canary fails either way), with the telemetry-derived acceptance
     report riding along (``serve/telemetry_report``).

  8. sampled-serving economics (docs/serving.md): a SAMPLED build
     (stochastic verify fused into the same round executables) vs the
     greedy build on the same stream — dispatch/sync discipline must be
     IDENTICAL per round (exact equality: 1 donated dispatch, 1 drain per
     single-mode round, sampled or not — the runtime twin of the sampled
     dispatch contracts) and rounds/s must stay within 10%
     (``serve/sampled_vs_greedy``; the smoke canary fails either way).

All variants are lossless (greedy output == AR exactly; sampled output ==
the target distribution in law), so tokens/step and round latency are the
whole story.
"""
from __future__ import annotations

import sys
import time

import numpy as np

from repro.core.dsia import layer_sparsity
from repro.serving import BatchedSpecServer, Request, RequestScheduler, ServeLoop

sys.path.insert(0, "benchmarks")
from common import CACHE_DIR, csv_line, task_prompts, trained_params

MAX_BATCH = 4
DRAFT_K = 4


def _serve_stream(cfg, params, prompts, n_tokens, *, mode, adaptive,
                  with_summary=False, passes=1, **srv_kw):
    kw = (
        # default mixing hierarchy: a layer-sparsity level + an int8 level
        {} if mode == "cascade_fused"
        else {"draft_spec": layer_sparsity(cfg, 0.5)}
    )
    kw.update(srv_kw)
    max_batch = kw.pop("max_batch", MAX_BATCH)
    max_len = kw.pop("max_len", 512)
    srv = BatchedSpecServer(cfg, params, max_batch=max_batch, max_len=max_len,
                            draft_k=DRAFT_K,
                            mode=mode, adaptive=adaptive, **kw)

    def one_pass():
        sched = RequestScheduler(max_batch=max_batch)
        for p in prompts:
            sched.submit(Request(prompt=p[:48], max_new_tokens=n_tokens))
        t0 = time.perf_counter()
        steps0, tokens0 = srv.stats["steps"], srv.stats["tokens"]
        wait0, syncs0 = srv.stats["device_wait"], srv.stats["host_syncs"]
        rdisp0 = srv.stats["round_dispatches"]
        ServeLoop(srv, sched).run()
        srv.flush()                 # drain pipelined tails into this pass
        return (time.perf_counter() - t0,
                srv.stats["steps"] - steps0, srv.stats["tokens"] - tokens0,
                srv.stats["device_wait"] - wait0,
                srv.stats["host_syncs"] - syncs0,
                srv.stats["round_dispatches"] - rdisp0)

    one_pass()                      # warmup: compiles every scan-length variant
    # best-of-``passes`` on wall time: identical work each pass (fixed
    # stream, greedy), so the fastest pass is the least-noise estimate —
    # the timing-sensitive A/Bs (telemetry overhead) use passes=2
    results = [one_pass() for _ in range(max(passes, 1))]
    wall, steps, tokens, dev_wait, syncs, rdisp = min(results,
                                                      key=lambda r: r[0])
    steps = max(steps, 1)
    r = {
        "tokens_per_step": tokens / steps,
        "us_per_round": wall / steps * 1e6,
        "rounds_per_s": steps / max(wall, 1e-9),
        "draft_dispatches_per_round": srv.stats["draft_dispatches"] / max(srv.stats["steps"], 1),
        # host-overhead breakdown: device_us = wall the host spent BLOCKED
        # on device results, host_us = everything else (python bookkeeping,
        # dispatch, retrieval). A pipelined round hides both behind the
        # in-flight dispatches, so its host_us is the true overhead story.
        "device_us_per_round": dev_wait / steps * 1e6,
        "host_us_per_round": (wall - dev_wait) / steps * 1e6,
        "host_syncs_per_round": syncs / steps,
        # raw per-pass dispatch/sync counts: the telemetry-overhead arm
        # pins these to EXACT equality between telemetry on and off
        "round_dispatches": rdisp,
        "host_syncs": syncs,
        "steps": steps,
    }
    if with_summary:
        # telemetry-derived report (docs/observability.md) — cumulative
        # over warmup + timed pass, drained at this sync point only
        r["telemetry"] = srv.metrics_summary()
    return r


def main(n_tokens: int = 32, smoke: bool = False) -> dict:
    # draft-KV carry vs full-block recompute at the N=32 tree bucket: the
    # same stream drafted both ways MUST accept identical tokens/step
    # (deterministic parity canary) while carry decodes <= top_k tokens
    # per expansion instead of the 32-wide padded block (rounds/s A/B)
    n32 = (("tree_carry_n32", "tree_fused", False,
            {"tree_bucket": 32, "draft_kv": "carry"}),
           ("tree_recompute_n32", "tree_fused", False,
            {"tree_bucket": 32, "draft_kv": "recompute"}))
    if smoke:
        # tiny model (half-depth, briefly trained), few rounds: the CI
        # drafting-path canary, cached apart from the full bench model
        import dataclasses

        from common import bench_config

        n_tokens = min(n_tokens, 8)
        cfg = dataclasses.replace(bench_config(), num_layers=4)
        cfg, params = trained_params(cfg, steps=12,
                                     cache_dir=CACHE_DIR + "_smoke")
        prompts = [p for ps in task_prompts(cfg, 1).values() for p in ps][:4]
        variants = (("fused", "chain_fused", False, {}),
                    ("tree", "tree_fused", False, {}),
                    ("cascade", "cascade_fused", False, {})) + n32
    else:
        cfg, params = trained_params()
        prompts = [p for ps in task_prompts(cfg, 2).values() for p in ps][:8]
        # fused-vs-seedloop is a pure dispatch A/B (identical draft
        # semantics); tree-vs-fused is the DyTC structure A/B; *_adaptive
        # additionally lets Eq. 5 budgets trim per-slot drafting online
        variants = (("fused", "chain_fused", False, {}),
                    ("seedloop", "legacy", False, {}),
                    ("fused_adaptive", "chain_fused", True, {}),
                    ("tree", "tree_fused", False, {}),
                    ("tree_adaptive", "tree_fused", True, {}),
                    ("cascade", "cascade_fused", False, {}),
                    ("cascade_adaptive", "cascade_fused", True, {})) + n32
    out = {}
    for name, mode, adaptive, extra in variants:
        r = _serve_stream(cfg, params, prompts, n_tokens,
                          mode=mode, adaptive=adaptive, **extra)
        out[name] = r
        print(csv_line(
            f"serve/{name}", r["us_per_round"],
            f"tokens_per_step={r['tokens_per_step']:.3f};"
            f"draft_dispatches_per_round={r['draft_dispatches_per_round']:.2f}",
        ))
    # round-pipeline A/B (question 5): the STEADY-STATE host-gated round —
    # adaptive routing under an unmeetable t_min stops neural drafting
    # after one observation on BOTH paths (deterministic same-regime A/B,
    # independent of per-machine cost coefficients), leaving PLD retrieval
    # + routing + verify per round: exactly the per-round host overhead the
    # single-dispatch path moves on device. B=8 slots and a lean cache
    # keep the device share small so the overhead story is measurable on
    # CPU; the donate arm re-runs single with buffer donation forced ON
    # (the CPU default is off — donating an in-flight round's output
    # serializes async dispatch) for the exact-parity canary.
    round_prompts = [p for ps in task_prompts(cfg, 2).values() for p in ps][:8]
    for name, extra in (
        ("round_split", {"round_mode": "split"}),
        ("round_single", {"round_mode": "single", "sync_every": 4}),
        ("round_single_donate",
         {"round_mode": "single", "sync_every": 4, "donate": True}),
    ):
        r = _serve_stream(
            cfg, params, round_prompts, max(n_tokens, 16),
            mode="chain_fused", adaptive=True, min_obs=1, t_min=10.0,
            max_batch=8, max_len=192, **extra,
        )
        out[name] = r
        print(csv_line(
            f"serve/{name}", r["us_per_round"],
            f"tokens_per_step={r['tokens_per_step']:.3f};"
            f"host_us={r['host_us_per_round']:.1f};"
            f"device_us={r['device_us_per_round']:.1f};"
            f"syncs_per_round={r['host_syncs_per_round']:.2f}",
        ))
    if "seedloop" in out:
        speedup = out["seedloop"]["us_per_round"] / max(out["fused"]["us_per_round"], 1e-9)
        print(csv_line("serve/fused_round_speedup", out["fused"]["us_per_round"],
                       f"round_speedup={speedup:.3f}"))
        out["round_speedup"] = speedup
    # DyTC §4.2 headline: tree drafting must accept at least as many
    # tokens/step as chain drafting on the same stream
    ratio = out["tree"]["tokens_per_step"] / max(out["fused"]["tokens_per_step"], 1e-9)
    print(csv_line("serve/tree_vs_chain", out["tree"]["us_per_round"],
                   f"accept_ratio={ratio:.3f};"
                   f"tree_tps={out['tree']['tokens_per_step']:.3f};"
                   f"chain_tps={out['fused']['tokens_per_step']:.3f}"))
    out["tree_accept_ratio"] = ratio
    if ratio < 1.0:
        print(f"WARNING: tree accepted fewer tokens/step than chain ({ratio:.3f})")
    # §4.1/Alg. 1 headline: the multi-level cascade must accept at least as
    # many tokens/step as single-level tree drafting on the same stream
    c_ratio = (out["cascade"]["tokens_per_step"]
               / max(out["tree"]["tokens_per_step"], 1e-9))
    print(csv_line("serve/cascade_vs_tree", out["cascade"]["us_per_round"],
                   f"accept_ratio={c_ratio:.3f};"
                   f"cascade_tps={out['cascade']['tokens_per_step']:.3f};"
                   f"tree_tps={out['tree']['tokens_per_step']:.3f}"))
    out["cascade_accept_ratio"] = c_ratio
    if c_ratio < 1.0:
        print(f"WARNING: cascade accepted fewer tokens/step than tree ({c_ratio:.3f})")
    # staged-KV carry headline at N=32: identical tokens/step by parity
    # (deterministic canary) and rounds/s at least as good as recompute
    # (timing — reported, warned on, but never a hard failure on shared
    # runners)
    ck, rk = out["tree_carry_n32"], out["tree_recompute_n32"]
    carry_speed = rk["us_per_round"] / max(ck["us_per_round"], 1e-9)
    kv_parity = ck["tokens_per_step"] / max(rk["tokens_per_step"], 1e-9)
    print(csv_line("serve/carry_vs_recompute_n32", ck["us_per_round"],
                   f"round_speedup={carry_speed:.3f};tps_parity={kv_parity:.3f};"
                   f"carry_tps={ck['tokens_per_step']:.3f};"
                   f"recompute_tps={rk['tokens_per_step']:.3f}"))
    out["carry_speedup_n32"] = carry_speed
    out["carry_tps_parity_n32"] = kv_parity
    if carry_speed < 1.0:
        print(f"WARNING: carry rounds slower than recompute at N=32 ({carry_speed:.3f})")
    # round-pipeline headline: the single-dispatch pipelined round vs the
    # split draft/verify round — rounds/s is the story (the host-overhead
    # breakdown rides along), tokens/step must match (both are the same
    # lossless drafts). The donated-vs-nondonated tps parity is exact by
    # construction (donation is pure aliasing) and is the deterministic
    # canary here.
    sg, sp = out["round_single"], out["round_split"]
    single_speed = sp["us_per_round"] / max(sg["us_per_round"], 1e-9)
    print(csv_line(
        "serve/round_single_vs_split", sg["us_per_round"],
        f"round_speedup={single_speed:.3f};"
        f"single_host_us={sg['host_us_per_round']:.1f};"
        f"single_device_us={sg['device_us_per_round']:.1f};"
        f"split_host_us={sp['host_us_per_round']:.1f};"
        f"split_device_us={sp['device_us_per_round']:.1f};"
        f"single_syncs_per_round={sg['host_syncs_per_round']:.2f}",
    ))
    out["single_round_speedup"] = single_speed
    donate_parity = (sg["tokens_per_step"]
                     / max(out["round_single_donate"]["tokens_per_step"], 1e-9))
    print(csv_line("serve/donate_tps_parity", sg["us_per_round"],
                   f"tps_parity={donate_parity:.4f}"))
    out["donate_tps_parity"] = donate_parity
    if single_speed < 1.15:
        print(f"WARNING: single-dispatch round below the 1.15x target "
              f"vs split ({single_speed:.3f})")
    # telemetry-overhead A/B (docs/observability.md): the device-carried
    # telemetry buffer rides the SAME single-dispatch round, so enabling
    # it must add ZERO dispatches and ZERO host syncs (exact equality —
    # deterministic, the runtime twin of assert_telemetry_transparent)
    # and must keep rounds/s within 5% of the disabled server (timing).
    telem_kw = dict(mode="chain_fused", adaptive=True, min_obs=1, t_min=10.0,
                    max_batch=8, max_len=192,
                    round_mode="single", sync_every=4)
    t_on = _serve_stream(cfg, params, round_prompts, max(n_tokens, 16),
                         telemetry=True, with_summary=True, passes=2,
                         **telem_kw)
    t_off = _serve_stream(cfg, params, round_prompts, max(n_tokens, 16),
                          telemetry=False, passes=2, **telem_kw)
    out["telemetry_on"], out["telemetry_off"] = t_on, t_off
    telem_speed = t_on["rounds_per_s"] / max(t_off["rounds_per_s"], 1e-9)
    telem_transparent = (
        t_on["round_dispatches"] == t_off["round_dispatches"]
        and t_on["host_syncs"] == t_off["host_syncs"]
    )
    print(csv_line(
        "serve/telemetry_overhead", t_on["us_per_round"],
        f"rounds_ratio={telem_speed:.3f};"
        f"transparent={int(telem_transparent)};"
        f"on_dispatches={t_on['round_dispatches']};"
        f"off_dispatches={t_off['round_dispatches']};"
        f"on_syncs={t_on['host_syncs']};off_syncs={t_off['host_syncs']}",
    ))
    out["telemetry_rounds_ratio"] = telem_speed
    out["telemetry_transparent"] = telem_transparent
    summ = t_on["telemetry"]
    print(csv_line(
        "serve/telemetry_report", t_on["us_per_round"],
        f"tokens_per_step={summ['tokens_per_step']:.3f};"
        f"accepted={sum(summ['accepted_per_slot'])};"
        f"drafted={sum(summ['drafted_per_slot'])};"
        f"pld_tokens={sum(summ['pld_tokens_per_slot'])};"
        f"device_wait_s={summ['device_wait_s']:.3f}",
    ))
    if telem_speed < 0.95:
        print(f"WARNING: telemetry-on rounds/s below 0.95x of disabled "
              f"({telem_speed:.3f})")
    # sampled-vs-greedy A/B (question 8): the stochastic verify is fused
    # INTO the round executable (PRNG split + acceptance draws on device),
    # so a sampled build must keep the exact single-dispatch discipline —
    # round_dispatches == steps and host_syncs == steps on BOTH builds
    # (sync_every=1: one drain per round, nothing in flight at admission)
    # — and rounds/s within 10% of greedy on the same stream.
    from repro.serving.sampler import SamplingParams

    samp_kw = dict(mode="chain_fused", adaptive=False, round_mode="single",
                   passes=2)
    s_on = _serve_stream(cfg, params, prompts, n_tokens,
                         sampling=SamplingParams(temperature=0.8, top_k=20,
                                                 top_p=0.9, seed=7),
                         **samp_kw)
    s_off = _serve_stream(cfg, params, prompts, n_tokens, **samp_kw)
    out["sampled_on"], out["sampled_off"] = s_on, s_off
    sampled_speed = s_on["rounds_per_s"] / max(s_off["rounds_per_s"], 1e-9)
    sampled_transparent = (
        s_on["round_dispatches"] == s_on["steps"]
        and s_off["round_dispatches"] == s_off["steps"]
        and s_on["host_syncs"] == s_on["steps"]
        and s_off["host_syncs"] == s_off["steps"]
    )
    print(csv_line(
        "serve/sampled_vs_greedy", s_on["us_per_round"],
        f"rounds_ratio={sampled_speed:.3f};"
        f"transparent={int(sampled_transparent)};"
        f"sampled_tps={s_on['tokens_per_step']:.3f};"
        f"greedy_tps={s_off['tokens_per_step']:.3f};"
        f"sampled_dispatches={s_on['round_dispatches']};"
        f"sampled_syncs={s_on['host_syncs']}",
    ))
    out["sampled_rounds_ratio"] = sampled_speed
    out["sampled_transparent"] = sampled_transparent
    if sampled_speed < 0.90:
        print(f"WARNING: sampled rounds/s below 0.90x of greedy "
              f"({sampled_speed:.3f})")
    shard_parity = 1.0
    paged_parity, paged_overlap = 1.0, 1
    if smoke:
        shard_parity = _sharded_arm(out)
        paged_parity, paged_overlap = _paged_arm(cfg, params, out)
    if smoke and (ratio < 0.9 or c_ratio < 0.9
                  or not (0.97 <= kv_parity <= 1.03)
                  or not (0.999 <= shard_parity <= 1.001)
                  or not (0.999 <= donate_parity <= 1.001)
                  or not (0.999 <= paged_parity <= 1.001)
                  or paged_overlap <= 0
                  or telem_speed < 0.95 or not telem_transparent
                  or sampled_speed < 0.90 or not sampled_transparent):
        # the canaries must be able to FAIL: tokens/step is deterministic
        # for a fixed stream/model (no timing noise), so a clear
        # accept-ratio regression exits nonzero and marks the non-blocking
        # CI job red. The measured numbers ride on the exception so the
        # uploaded bench.json still carries them (benchmarks/run.py).
        # (carry/recompute tps parity tolerates 3% for softmax-merge ULP
        # near-ties on a freshly trained model; real divergence is larger.)
        err = SystemExit(
            f"smoke canary: accept ratio below 0.9 or a parity broken "
            f"(tree/chain {ratio:.3f}, cascade/tree {c_ratio:.3f}, "
            f"carry/recompute tps {kv_parity:.3f}, "
            f"sharded/single tps {shard_parity:.4f}, "
            f"donated/nondonated tps {donate_parity:.4f}, "
            f"telemetry rounds/s {telem_speed:.3f} "
            f"transparent={telem_transparent}, "
            f"sampled rounds/s {sampled_speed:.3f} "
            f"transparent={sampled_transparent}, "
            f"paged/dense tps {paged_parity:.4f}, "
            f"chunked overlap tokens {paged_overlap})"
        )
        err.results = out
        raise err
    return out


def _paged_arm(cfg, params, out: dict):
    """Question 9 (docs/paging.md): block-paged KV + chunked prefill.

    Two canaries on the SAME bursty heavy-tailed load-gen trace:

      a. ``serve/paged_vs_dense`` — a paged server must route EXACTLY the
         token streams of the dense server (paging is placement, never
         math), with tokens/round parity recorded into the trend;
      b. ``serve/chunked_prefill_overlap`` — with ``prefill_chunk`` on, a
         LONG prompt admitted mid-stream must NOT stall the loop: other
         slots keep routing tokens during the rounds its prompt is still
         chunk-prefilling (overlap tokens > 0 — the non-blocking-admission
         headline), with rounds + TTFT-in-rounds reported alongside.
    """
    import load_gen

    from repro.serving import BatchedSpecServer

    trace = load_gen.heavy_tailed_trace(
        vocab=cfg.vocab_size, n_requests=16, seed=11,
        rate=0.7, prompt_max=96, out_max=16,
    )
    runs = {}
    for name, kw in (
        ("dense", {}),
        ("paged", {"paged": True, "page_size": 64}),
    ):
        srv = BatchedSpecServer(
            cfg, params, max_batch=MAX_BATCH, max_len=256, draft_k=DRAFT_K,
            draft_spec=layer_sparsity(cfg, 0.5), mode="chain_fused",
            adaptive=False, **kw,
        )
        t0 = time.perf_counter()
        runs[name] = load_gen.run_trace(srv, trace, max_batch=MAX_BATCH)
        runs[name]["us_per_round"] = (
            (time.perf_counter() - t0) * 1e6 / max(runs[name]["rounds"], 1)
        )
    exact = runs["paged"]["token_streams"] == runs["dense"]["token_streams"]
    parity = (runs["paged"]["tokens_per_round"]
              / max(runs["dense"]["tokens_per_round"], 1e-9)) if exact else 0.0
    out["paged_run"], out["dense_run"] = (
        {k: v for k, v in runs[n].items()
         if k not in ("finished", "token_streams")}
        for n in ("paged", "dense")
    )
    # trend-shaped rows (tokens_per_step + us_per_round): the tokens/round
    # parity of the paged build rides BENCH_smoke.json alongside the other
    # serve variants
    for name in ("dense", "paged"):
        out[f"loadgen_{name}"] = {
            "tokens_per_step": runs[name]["tokens_per_round"],
            "us_per_round": runs[name]["us_per_round"],
        }
    print(csv_line(
        "serve/paged_vs_dense", runs["paged"]["us_per_round"],
        f"tps_parity={parity:.4f};exact_streams={int(exact)};"
        + load_gen.summarize(runs["paged"]),
    ))
    out["paged_tps_parity"] = parity

    # (b) three short prompts decode while one long prompt chunk-prefills
    rng = np.random.default_rng(5)
    shorts = [
        load_gen.TraceRequest(0, rng.integers(
            1, cfg.vocab_size, size=12).astype(np.int32), 24)
        for _ in range(3)
    ]
    long_req = load_gen.TraceRequest(2, rng.integers(
        1, cfg.vocab_size, size=192).astype(np.int32), 8)
    srv = BatchedSpecServer(
        cfg, params, max_batch=MAX_BATCH, max_len=256, draft_k=DRAFT_K,
        draft_spec=layer_sparsity(cfg, 0.5), mode="chain_fused",
        adaptive=False, paged=True, page_size=64, prefill_chunk=16,
    )
    t0 = time.perf_counter()
    rep = load_gen.run_trace(srv, shorts + [long_req], max_batch=MAX_BATCH)
    rep["us_per_round"] = (
        (time.perf_counter() - t0) * 1e6 / max(rep["rounds"], 1)
    )
    # tokens routed to OTHER requests while the long prompt was still
    # prefilling: every token before its first token is someone else's
    long_first = rep["ttft_rounds_max"]    # the 192-token prompt dominates
    overlap = int(sum(rep["routed_per_round"][2:2 + int(long_first)]))
    print(csv_line(
        "serve/chunked_prefill_overlap", rep["us_per_round"],
        f"overlap_tokens={overlap};long_ttft_rounds={long_first};"
        + load_gen.summarize(rep),
    ))
    out["chunked_overlap_tokens"] = overlap
    out["loadgen_chunked_prefill"] = {
        "tokens_per_step": rep["tokens_per_round"],
        "us_per_round": rep["us_per_round"],
    }
    out["chunked_run"] = {
        k: v for k, v in rep.items() if k not in ("finished", "token_streams")
    }
    return parity, overlap


_SHARD_SCRIPT = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import dataclasses, json, sys
sys.path.insert(0, "benchmarks")
from serve_batched import _serve_stream
from common import CACHE_DIR, bench_config, task_prompts, trained_params
from repro.launch.mesh import make_mesh

cfg = dataclasses.replace(bench_config(), num_layers=4)
cfg, params = trained_params(cfg, steps=12, cache_dir=CACHE_DIR + "_smoke")
prompts = [p for ps in task_prompts(cfg, 1).values() for p in ps][:4]
mesh = make_mesh((4, 2), ("data", "model"))
out = {}
for name, mesh_kw in (("single", {}), ("sharded", {"mesh": mesh})):
    out[name] = _serve_stream(cfg, params, prompts, 8,
                              mode="chain_fused", adaptive=False, **mesh_kw)
print(json.dumps(out))
"""


def _sharded_arm(out: dict) -> float:
    """Question 6: the sharded-vs-single round A/B, in a subprocess (the
    forced host-device count must be set before jax initializes, and the
    parent bench must keep seeing the real devices). Reuses the parent's
    smoke model cache; both variants land in ``out`` with the
    us_per_round/tokens_per_step keys ``trend.py`` records.

    The child is a CPU-only A/B: it runs only from a CPU parent, because a
    parent on an accelerator holds its chip and a JAX child would then
    fail or hang."""
    import json
    import os
    import subprocess

    import jax

    if jax.default_backend() != "cpu":
        raise RuntimeError(
            "the sharded arm forces 8 CPU devices in a child process and "
            f"runs only from a CPU parent (this one is on "
            f"{jax.default_backend()})"
        )

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(root, "src"), os.path.join(root, "benchmarks")]
    )
    proc = subprocess.run(
        [sys.executable, "-c", _SHARD_SCRIPT], capture_output=True,
        text=True, env=env, cwd=root, timeout=900,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"sharded arm subprocess failed:\n{proc.stderr[-2000:]}"
        )
    res = json.loads(proc.stdout.strip().splitlines()[-1])
    sg, sh = res["single"], res["sharded"]
    out["mesh_single_base"], out["mesh_sharded_n8"] = sg, sh
    parity = sh["tokens_per_step"] / max(sg["tokens_per_step"], 1e-9)
    overhead = sh["us_per_round"] / max(sg["us_per_round"], 1e-9)
    print(csv_line(
        "serve/sharded_vs_single", sh["us_per_round"],
        f"tps_parity={parity:.4f};round_overhead={overhead:.3f};"
        f"sharded_tps={sh['tokens_per_step']:.3f};"
        f"single_tps={sg['tokens_per_step']:.3f}",
    ))
    out["sharded_tps_parity"] = parity
    out["sharded_round_overhead"] = overhead
    return parity


if __name__ == "__main__":
    main()
