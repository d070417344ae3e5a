"""End-to-end driver: batched multi-level cascade serving.

  PYTHONPATH=src python examples/serve_cascade.py

Serves a small model over a stream of Spec-Bench-style requests (mixed
tasks) with the paper's namesake ``cascade_fused`` mode: a DSIA hierarchy
(layer-sparsity level + int8 activation-quant level + PLD) materialized by
the draft bank, the cheapest level drafting every slot's tree in one scan,
the stronger level rescoring in one intermediate-verify dispatch, one
joint target verify per round, per-slot Eq. 5 routing across levels.
Reports throughput (tokens/step) and verifies every completed request
against its own single-stream AR reference.
"""
import dataclasses
import sys
import time

sys.path.insert(0, "src")

import jax
import numpy as np

from repro.config import get_config
from repro.core.cascade import ARScheduler
from repro.core.engine import SpecEngine
from repro.data import SPEC_TASKS, make_task_prompts
from repro.models import init_params
from repro.serving import BatchedSpecServer, Request, RequestScheduler, ServeLoop

cfg = dataclasses.replace(get_config("vicuna-7b").reduced(), num_layers=6)
params = init_params(cfg, jax.random.PRNGKey(0))

# a request stream across tasks
requests = []
for task in ("summarization", "qa", "rag", "translation"):
    for p in make_task_prompts(SPEC_TASKS[task], 2, cfg.vocab_size, seed=3):
        requests.append(Request(prompt=p[:48], max_new_tokens=32))

MAX_BATCH = 4
srv = BatchedSpecServer(cfg, params, max_batch=MAX_BATCH, max_len=512,
                        draft_k=4, mode="cascade_fused")
print("cascade levels:", " > ".join(l.name for l in srv.bank.levels), "> PLD",
      f"(int8 copy: {srv.bank.param_bytes/1e6:.1f} MB)")
sched = RequestScheduler(max_batch=MAX_BATCH)
for r in requests:
    sched.submit(r)

t0 = time.perf_counter()
finished = ServeLoop(srv, sched).run()
elapsed = time.perf_counter() - t0
steps = srv.stats["steps"]

print(f"served {len(requests)} requests in {steps} steps, {elapsed:.1f}s")
print(f"throughput: {srv.stats['tokens'] / steps:.2f} accepted tokens/step "
      f"(batch={MAX_BATCH})")
print(f"dispatches/round: "
      f"{srv.stats['draft_dispatches'] / max(steps, 1):.2f} draft + "
      f"{srv.stats['rescore_dispatches'] / max(steps, 1):.2f} rescore "
      f"(bounded: one per cascade level — the target verify rides the "
      f"last rescore dispatch)")

# verify losslessness of every completed request
bad = 0
for req in finished:
    eng = SpecEngine(cfg, params, max_len=512)
    eng.start(req.prompt)
    ref = ARScheduler(eng).generate(len(req.generated))
    bad += ref != req.generated
print(f"lossless requests: {len(finished) - bad}/{len(finished)}")
assert bad == 0
