"""Multi-level DSIA draft cascade in the batched server (`cascade_fused`):
losslessness vs the B=1 AR reference, bounded dispatches per round (one per
cascade level + one target verify), Eq. 5 multi-level routing collapse,
draft-bank materialization, and the level-to-level rescore semantics."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import get_config
from repro.core.cascade import ARScheduler
from repro.core.dsia import (
    activation_quant,
    build_hierarchy,
    layer_sparsity,
    streaming_attention,
)
from repro.core.engine import SpecEngine, cascade_rescore
from repro.core.ewif import t_cascade, t_sd
from repro.core.latency import best_cascade_plan
from repro.core.tree import tree_seed_arrays
from repro.models import model as M
from repro.serving.draft_bank import DraftBank
from repro.serving.server import BatchedSpecServer

CFG = dataclasses.replace(get_config("vicuna-7b").reduced(), num_layers=4)
PARAMS = M.init_params(CFG, jax.random.PRNGKey(0))
HIER = build_hierarchy(CFG, "mixing")      # LS + LS+int8 + PLD


def _random_prompts(n, length, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(4, CFG.vocab_size - 1, size=length).astype(np.int32)
            for _ in range(n)]


def _repetitive_prompts():
    return [
        np.array([5, 6, 7, 8] * 4, np.int32),
        np.array([9, 10, 11] * 5, np.int32),
    ]


def _assert_matches_ar(srv, prompts, rounds):
    for i, p in enumerate(prompts):
        srv.add_request(i, p)
    gen = {i: [] for i in range(len(prompts))}
    for _ in range(rounds):
        for b, toks in srv.step().items():
            gen[b].extend(toks)
    for i, p in enumerate(prompts):
        eng = SpecEngine(CFG, PARAMS, max_len=256)
        eng.start(p)
        ref = ARScheduler(eng).generate(len(gen[i]))
        assert ref == gen[i], f"slot {i} diverged"
    return gen


# ------------------------------------------------------------- losslessness
def test_cascade_fused_matches_single_stream():
    """cascade_fused with the default mixing hierarchy (layer-sparsity level
    + int8 activation-quant level) must emit exactly the B=1 greedy stream
    for every slot."""
    srv = BatchedSpecServer(CFG, PARAMS, max_batch=2, max_len=256, draft_k=4,
                            mode="cascade_fused", adaptive=True, min_obs=1)
    # the acceptance-criteria hierarchy shape: >= 2 executable levels, one
    # gates-only and one int8
    assert len(srv.bank) >= 2
    assert any(l.quantize == "int8" or l.owns_params for l in srv.bank.levels)
    assert any(l.gates is not None and not l.owns_params and l.quantize is None
               for l in srv.bank.levels)
    _assert_matches_ar(srv, _repetitive_prompts(), rounds=8)


def test_cascade_fused_lossless_random_prompts():
    """High-entropy prompts keep PLD silent: every node is neural (drafted
    by the cheapest level, rescored by the stronger one) and the committed
    output must still be token-identical to AR."""
    srv = BatchedSpecServer(CFG, PARAMS, max_batch=2, max_len=256, draft_k=4,
                            mode="cascade_fused", adaptive=False)
    _assert_matches_ar(srv, _random_prompts(2, 16, seed=3), rounds=6)


def test_cascade_fused_scaling_hierarchy_lossless():
    """A pure layer-sparsity (scaling) hierarchy is lossless too — the
    invariant holds for every hierarchy mode."""
    srv = BatchedSpecServer(CFG, PARAMS, max_batch=2, max_len=256, draft_k=4,
                            mode="cascade_fused", adaptive=False,
                            hierarchy=build_hierarchy(CFG, "scaling"))
    _assert_matches_ar(srv, _repetitive_prompts(), rounds=6)


# ------------------------------------------------------- dispatch discipline
def test_bounded_dispatches_per_round():
    """Per round: ONE drafting scan + ONE rescore per stronger level, with
    the target verify riding the LAST rescore dispatch — never more,
    regardless of per-slot routing."""
    srv = BatchedSpecServer(CFG, PARAMS, max_batch=2, max_len=256, draft_k=4,
                            mode="cascade_fused", adaptive=False)
    n_levels = len(srv.bank)
    for i, p in enumerate(_random_prompts(2, 24)):
        srv.add_request(i, p)
    n_rounds = 5
    for _ in range(n_rounds):
        srv.step()
    assert srv.stats["draft_dispatches"] == n_rounds
    assert srv.stats["rescore_dispatches"] == n_rounds * (n_levels - 1)
    assert srv.stats["target_calls"] == n_rounds
    assert len(srv._casc_draft_fns) == 1      # fixed budget -> one compile
    # bounded compile caches: one executable per rescoring level (the
    # strongest level's carries the folded target verify)
    assert (len(srv._rescore_fns) + len(srv._rescore_verify_fns)
            == n_levels - 1)


def test_cascade_budget_collapses_to_pld_only():
    """An unmeetable t_min drives the Eq. 5 plan to PLD-only: no drafting
    scan, no rescore — and the output stays lossless (plain AR inside the
    same batched verify)."""
    srv = BatchedSpecServer(CFG, PARAMS, max_batch=2, max_len=256, draft_k=4,
                            mode="cascade_fused", adaptive=True, min_obs=1,
                            t_min=1e9)
    _assert_matches_ar(srv, _random_prompts(2, 16, seed=5), rounds=6)
    exp, use_rescore, _, _ = srv._slot_cascade_plan(0)
    assert exp == 0 and not use_rescore
    # once every slot is warmed up, rounds stop dispatching neural work
    d0, r0 = srv.stats["draft_dispatches"], srv.stats["rescore_dispatches"]
    srv.step()
    assert srv.stats["draft_dispatches"] == d0
    assert srv.stats["rescore_dispatches"] == r0


def test_single_level_hierarchy_still_adapts():
    """A 1-level hierarchy has no rescorer, so slot_key(0) is fed through
    the single-level (direct) observation path — the warm-up gate must not
    starve and the PLD-only collapse must still engage."""
    hier = [layer_sparsity(CFG, 0.5), build_hierarchy(CFG, "mixing")[-1]]
    srv = BatchedSpecServer(CFG, PARAMS, max_batch=1, max_len=256, draft_k=4,
                            mode="cascade_fused", adaptive=True, min_obs=1,
                            t_min=1e9, hierarchy=hier)
    assert len(srv.bank) == 1
    srv.add_request(0, _random_prompts(1, 16, seed=9)[0])
    for _ in range(4):
        srv.step()
    exp, use_rescore, _, _ = srv._slot_cascade_plan(0)
    assert exp == 0 and not use_rescore
    d0 = srv.stats["draft_dispatches"]
    srv.step()
    assert srv.stats["draft_dispatches"] == d0
    assert srv.stats["rescore_dispatches"] == 0


def test_cascade_plan_routes_single_level():
    """When the rescorer's own acceptance is no better than the cheap
    level's direct acceptance, the plan drops the rescore dispatch."""
    # strong level adds nothing (alpha_direct == product) but costs c0
    exp, use_rescore = best_cascade_plan(
        [0.9, 0.9], [0.5, 0.1], alpha_direct=0.81, e_max=6, t_min=1.0
    )
    assert exp > 0 and not use_rescore
    # a cheap strong level with high acceptance over a weak drafter: rescore
    exp2, use2 = best_cascade_plan(
        [0.95, 0.5], [0.05, 0.04], alpha_direct=0.3, e_max=6, t_min=1.0
    )
    assert exp2 > 0 and use2
    # nothing pays -> PLD-only
    assert best_cascade_plan([0.05, 0.05], [0.9, 0.9], 0.01, 6, 1.05) == (0, False)


def test_t_cascade_degenerates_to_t_sd():
    for a, c, k in [(0.7, 0.3, 4), (0.9, 0.1, 5), (0.2, 0.8, 3)]:
        assert t_cascade([a], [c], k) == pytest.approx(t_sd(a, c, k))
    with pytest.raises(ValueError):
        t_cascade([0.5], [0.1, 0.2], 3)


# ------------------------------------------------------------ spec handling
def test_unsupported_spec_fields_raise():
    """Gates-only modes must refuse quantize/attn_override specs instead of
    silently dropping them (they used to run gates-only)."""
    q_spec = activation_quant(CFG, 8, base=layer_sparsity(CFG, 0.5))
    for mode in ("chain_fused", "legacy", "tree_fused"):
        with pytest.raises(ValueError, match="cannot honor"):
            BatchedSpecServer(CFG, PARAMS, mode=mode, draft_spec=q_spec)
    sa_spec = streaming_attention(CFG, window=64)
    with pytest.raises(ValueError, match="attn_override"):
        BatchedSpecServer(CFG, PARAMS, mode="chain_fused", draft_spec=sa_spec)
    # plain gates specs stay accepted everywhere
    BatchedSpecServer(CFG, PARAMS, mode="tree_fused",
                      draft_spec=layer_sparsity(CFG, 0.5))


def test_cascade_mode_arg_validation():
    with pytest.raises(ValueError, match="hierarchy"):
        BatchedSpecServer(CFG, PARAMS, mode="cascade_fused",
                          draft_spec=layer_sparsity(CFG, 0.5))
    with pytest.raises(ValueError, match="cascade_fused"):
        BatchedSpecServer(CFG, PARAMS, mode="tree_fused", hierarchy=HIER)
    audio_cfg = dataclasses.replace(CFG, num_codebooks=4)
    with pytest.raises(ValueError, match="attention-only"):
        BatchedSpecServer(audio_cfg, PARAMS, mode="cascade_fused")


# --------------------------------------------------------------- draft bank
def test_draft_bank_materialization_sim_vs_kernel():
    bank_sim = DraftBank(CFG, PARAMS, HIER, int8_exec="sim")
    assert len(bank_sim) == 2
    strong, cheap = bank_sim.levels
    assert strong.gates is not None and not strong.owns_params
    assert strong.params is PARAMS            # gates-only levels share params
    assert cheap.owns_params and cheap.quantize is None
    assert cheap.params is not PARAMS         # one materialized int8 copy
    assert bank_sim.param_bytes > 0
    # the copy's MLP weights are actually fake-quantized
    w0 = PARAMS["segments"][0][0]["mlp"]["w_up"]
    wq = cheap.params["segments"][0][0]["mlp"]["w_up"]
    assert not np.allclose(np.asarray(w0), np.asarray(wq))

    bank_k = DraftBank(CFG, PARAMS, HIER, int8_exec="kernel")
    cheap_k = bank_k.levels[-1]
    assert cheap_k.quantize == "int8" and cheap_k.owns_params
    assert cheap_k.params is not PARAMS       # int8 MLP images, made once
    # int8 images: a quarter of the float32 weights, plus the scales
    assert 0 < bank_k.param_bytes < bank_sim.param_bytes

    with pytest.raises(ValueError, match="int8_exec"):
        DraftBank(CFG, PARAMS, HIER, int8_exec="gpu")
    with pytest.raises(ValueError, match="no neural level"):
        DraftBank(CFG, PARAMS, [HIER[-1]])


def test_draft_bank_priors_and_keys():
    bank = DraftBank(CFG, PARAMS, HIER, int8_exec="sim")
    assert bank.slot_key(0, 3) != bank.slot_key(1, 3)
    assert bank.slot_key(0, 0) != bank.slot_key(0, 1)
    # level-to-level prior >= the cheap level's target-facing prior
    assert bank.alpha_prior(1) >= bank.levels[1].spec.prior_alpha
    assert 0 < bank.direct_prior() <= bank.alpha_prior(0)
    assert bank.rescorers == [bank.levels[0]]
    assert bank.drafter is bank.levels[-1]


# ------------------------------------------------------- rescore semantics
def test_cascade_rescore_hedges_and_extends():
    """Level-to-level acceptance on a real (tiny) model: the rescored tree
    is a SUPERSET of the drafted tree (hedging, not overwriting), with this
    level's own continuation added as a sibling at the first mismatch and
    as a child of the deepest endorsed node."""
    rng = np.random.default_rng(7)
    prompt = rng.integers(2, CFG.vocab_size, size=12).astype(np.int32)
    cache = M.init_cache(CFG, 1, 64)
    last, cache = M.prefill(CFG, PARAMS, {"tokens": jnp.asarray(prompt[None])}, cache)
    pending = np.argmax(np.asarray(last), -1).astype(np.int32)
    # a 3-token chain the level will (almost surely) disagree with
    chains = rng.integers(2, CFG.vocab_size, size=(1, 4)).astype(np.int32)
    have = np.array([3], np.int32)
    seed = tree_seed_arrays(pending, chains, have, bucket=8)
    gates = jnp.asarray(layer_sparsity(CFG, 0.5).gates_array(CFG.num_layers))
    fn = jax.jit(functools.partial(cascade_rescore, CFG))
    out = fn(PARAMS, cache, *(jnp.asarray(a) for a in seed),
             jnp.asarray([1], jnp.int32),           # probe: first chain node
             jnp.asarray([True]),
             jnp.asarray([0.7], jnp.float32),
             gates)
    (tokens, parents, depth, p_acc, mask, count,
     level_node, probe_ok, probe_valid) = (np.asarray(a) for a in out)
    # the level's own argmax along the chain, for reference
    lg, _ = M.decode_step(CFG, PARAMS, cache, jnp.asarray(seed[0]),
                          gates=gates, tree_mask=jnp.asarray(seed[4]),
                          q_pos=cache["pos"][:, None] + jnp.asarray(seed[2]))
    nxt = np.argmax(np.asarray(lg)[0], -1)
    assert bool(probe_valid[0])                     # parent is the root
    agrees = int(chains[0, 0]) == int(nxt[0])
    assert bool(probe_ok[0]) == agrees
    # superset: every drafted node survives verbatim
    n0 = int(seed[5][0])
    np.testing.assert_array_equal(tokens[0, :n0], seed[0][0, :n0])
    np.testing.assert_array_equal(parents[0, :n0], seed[1][0, :n0])
    assert int(count[0]) >= n0
    if not agrees:
        # a hedge sibling of node 1 carries the level's root continuation
        # (and doubles as the frontier extension — root is the frontier)
        hedge = [i for i in range(n0, count[0])
                 if parents[0, i] == 0 and tokens[0, i] == int(nxt[0])]
        assert len(hedge) == 1
        assert int(level_node[0]) == hedge[0]
        assert int(depth[0, hedge[0]]) == 1
        # the hedge node sees exactly the root and itself
        assert set(np.flatnonzero(mask[0, hedge[0]])) == {0, hedge[0]}
    # apply=False slots pass through untouched
    out2 = fn(PARAMS, cache, *(jnp.asarray(a) for a in seed),
              jnp.asarray([1], jnp.int32), jnp.asarray([False]),
              jnp.asarray([0.7], jnp.float32), gates)
    np.testing.assert_array_equal(np.asarray(out2[0]), seed[0])
    np.testing.assert_array_equal(np.asarray(out2[1]), seed[1])
    assert int(np.asarray(out2[5])[0]) == int(seed[5][0])
    assert not bool(np.asarray(out2[8])[0])         # probe invalid when off


def test_cascade_and_tree_modes_agree_on_prefix():
    """Both modes are lossless, so their greedy streams must agree token
    for token on the shared prefix."""
    outs = []
    for mode, kw in (("cascade_fused", {}),
                     ("tree_fused", {"draft_spec": layer_sparsity(CFG, 0.5)})):
        srv = BatchedSpecServer(CFG, PARAMS, max_batch=2, max_len=256,
                                draft_k=4, mode=mode, adaptive=False, **kw)
        for i, p in enumerate(_repetitive_prompts()):
            srv.add_request(i, p)
        gen = {0: [], 1: []}
        for _ in range(6):
            for b, toks in srv.step().items():
                gen[b].extend(toks)
        outs.append(gen)
    for b in (0, 1):
        n = min(len(outs[0][b]), len(outs[1][b]))
        assert n > 0 and outs[0][b][:n] == outs[1][b][:n]
