"""Mesh-sharded batched serving: token identity + dispatch discipline.

Runs in a SUBPROCESS because the device count must be forced before jax
initializes (the rest of the suite must see the single real device). On a
forced 8-device CPU mesh (``data=4, model=2``) every server mode must:

  - produce greedy output token-identical to the same server on a single
    device (sharding is a placement decision, never a sampling one);
  - keep its dispatch discipline: ``round_dispatches``/``host_syncs``
    identical to the single-device run — the mesh adds collectives INSIDE
    the round executable, never extra dispatches or host syncs around it;
  - prove the placement on the COMPILED artifact: the single-dispatch
    chain/tree round keeps split entry-param shardings
    (``HloContract.assert_sharding``), stays donated, never re-enters the
    host, and carries no resharding all-to-alls (``assert_no_collectives``).

The sharded and single-device servers run in the SAME process on purpose:
the server's explicit per-server placements (``mesh=`` kwarg, no global
mesh) must not leak into servers constructed without a mesh.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

MODES = ["chain_fused", "legacy", "tree_fused", "cascade_fused"]

SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import dataclasses, json
    import jax
    import numpy as np
    from repro.analysis.contracts import server_round_contracts
    from repro.config import get_config
    from repro.core.dsia import layer_sparsity
    from repro.core.latency import CostTracker
    from repro.launch.mesh import make_mesh
    from repro.models import model as M
    from repro.serving.server import BatchedSpecServer

    CFG = dataclasses.replace(get_config("vicuna-7b").reduced(), num_layers=3)
    PARAMS = M.init_params(CFG, jax.random.PRNGKey(0))
    SPEC = layer_sparsity(CFG, 0.5)
    MESH = make_mesh((4, 2), ("data", "model"))
    B, ROUNDS = 4, 6
    # the adaptive planners price drafts from wall-clock costs; freeze them
    # at their priors so both placements plan the same rounds (otherwise
    # dispatch and sync counts agree only when the timings happen to)
    CostTracker.observe = lambda self, *a, **k: None
    CostTracker.observe_target = lambda self, *a, **k: None
    rng = np.random.default_rng(0)
    prompts = [rng.integers(2, CFG.vocab_size, size=n).astype(np.int32)
               for n in (8, 12, 6, 10)]

    def run(mode, mesh):
        kw = dict(max_batch=B, max_len=128, draft_k=4, tree_expansions=3,
                  adaptive=True, min_obs=1, donate=True)
        if mode != "cascade_fused":
            kw["draft_spec"] = SPEC
        srv = BatchedSpecServer(CFG, PARAMS, mode=mode, mesh=mesh, **kw)
        for i, p in enumerate(prompts):
            srv.add_request(i, p)
        gen = {i: [] for i in range(B)}
        for _ in range(ROUNDS):
            for b, t in srv.step().items():
                gen[b].extend(t)
        for b, t in srv.flush().items():
            gen[b].extend(t)
        return gen, srv

    results = {}
    for mode in ["chain_fused", "legacy", "tree_fused", "cascade_fused"]:
        g1, srv1 = run(mode, None)
        g2, srv2 = run(mode, MESH)
        res = {
            "identical": g1 == g2,
            "n_tokens": sum(len(v) for v in g1.values()),
            "round_dispatches": [srv1.stats["round_dispatches"],
                                 srv2.stats["round_dispatches"]],
            "host_syncs": [srv1.stats["host_syncs"], srv2.stats["host_syncs"]],
        }
        cons = server_round_contracts(srv2)
        for c in cons.values():
            c.assert_no_host_callbacks()
        if srv2.round_mode == "single":
            con = cons["round"]
            con.assert_donated().assert_sharding()
            con.assert_no_collectives("all-to-all")
            res["sharded_entry_params"] = len(con.sharded_params)
            res["collectives"] = con.collective_counts
            res["single_round"] = True
        else:
            res["sharded_entry_params"] = max(
                len(c.sharded_params) for c in cons.values()
            )
            res["single_round"] = False
        results[mode] = res
    print(json.dumps(results))
    """
)


@pytest.mark.slow
def test_sharded_serving_token_identity_and_contracts():
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT], capture_output=True, text=True,
        env=env, timeout=540,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert set(res) == set(MODES)
    for mode, r in res.items():
        # losslessness is placement-independent: greedy tokens must match
        # the single-device server exactly, for every slot
        assert r["identical"], f"{mode}: sharded tokens diverged"
        assert r["n_tokens"] > 0, f"{mode}: generated nothing"
        # the mesh never costs an extra dispatch or host sync
        assert r["round_dispatches"][0] == r["round_dispatches"][1], mode
        assert r["host_syncs"][0] == r["host_syncs"][1], mode
        # placement survived to the compiled executable
        assert r["sharded_entry_params"] > 0, f"{mode}: nothing sharded"
    # the tentpole: single-dispatch rounds stayed single-dispatch, donated,
    # sharded, and communicate only through TP collectives
    for mode in ("chain_fused", "tree_fused"):
        assert res[mode]["single_round"]
        assert any(k.startswith("all-") for k in res[mode]["collectives"]), (
            f"{mode}: no collectives — the model axis did nothing"
        )
