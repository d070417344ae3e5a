"""Batched speculative serving (continuous batching + cascades).

Four proposal modes (see docs/serving.md):

  - ``chain_fused``  — per-slot PLD proposals merged with a batched
    layer-sparse neural *chain* draft, one ``lax.scan`` dispatch per round
    (App. A's large-batch degradation path; the production default).
  - ``legacy``       — the seed's per-step chain drafting loop (one jitted
    dispatch + host sync per draft token); kept only as the A/B baseline.
  - ``tree_fused``   — the paper's headline Dynamic Tree Cascade (§4.2)
    run batched and on-device: every slot grows a bucketed token tree in a
    single fused ``tree_draft_scan`` dispatch, and tree verification +
    longest-accepted-path commit is one fused target call whose intra-tree
    attention can route through ``kernels.tree_attention``.
  - ``cascade_fused`` — the paper's namesake multi-level cascade (§4.1 +
    Alg. 1), batched: a ``DraftBank`` materializes a DSIA hierarchy
    (layer-sparsity gates, int8 activation-quant params, attention
    overrides), the CHEAPEST level grows every slot's tree in one scan
    dispatch, each stronger level rescores the proposal in one
    intermediate-verify dispatch (``core.engine.cascade_rescore`` —
    level-to-level endorsement, hedge siblings, and extension), and the
    target verifies + commits as in ``tree_fused``. Dispatches per round
    are bounded at (1 per cascade level) + 1 target verify. See
    docs/cascade.md.

All modes verify jointly in one target forward and commit per-sequence
(divergent accepted lengths are supported by the (B,)-pos cache).

Round execution (``round_mode=``): ``chain_fused``/``tree_fused`` run either
``"single"`` (the default) — ONE fused, device-resident jitted dispatch per
round (``core.engine.chain_round``/``tree_round``: device PLD over a carried
(B, max_len) context buffer, Eq. 4 EMAs + Eq. 5 budgets as carried device
arrays, draft + verify + accept + commit in one executable, cache and state
donated so the commit scatter aliases in place) — or ``"split"`` (the PR-4
structure: host PLD + one drafting dispatch + one verify dispatch with host
syncs between them; kept as the A/B baseline and the host-side oracle). In
single mode the host loop is a pipelined consumer: ``step()`` dispatches the
next round immediately and only drains accepted tokens from already-resolved
device futures every ``sync_every`` rounds (or on admission/retire), so
steady state has zero ``block_until_ready`` between rounds. ``legacy``
is always split (it IS the per-step baseline); ``cascade_fused`` keeps its
bounded one-dispatch-per-level structure but folds the target verify into
the last rescore dispatch (``core.engine.cascade_rescore_verify``) and
donates the cache into it.

Draft-KV execution (``draft_kv=``): the fused drafting scans run either in
``"recompute"`` (every step re-decodes the whole padded node block — O(E*N)
node-forwards per round) or ``"carry"`` (staged draft KV is carried in the
scan and each step decodes only the <= top_k newly appended tokens against
[committed cache ++ carried staged KV] — O(N + E*top_k)). ``"auto"`` picks
carry on attention-only stacks and recompute for SSM stacks, whose per-step
states cannot be carried row-wise. Both modes are token-identical
(tests/test_draft_kv_carry.py); carry is what lets tree buckets grow past
N=32 without the per-step block recompute eating the latency headroom.

Fused drafting
--------------
The k-step neural chain draft runs as ONE jitted ``lax.scan`` over draft
steps (``core.engine.chain_draft_scan``): each step re-decodes the fixed
(B, k+1) block under a causal tree mask, so later draft steps see earlier
drafted tokens through the staged-KV block path entirely on device, with
the committed cache read-only. One dispatch per proposal round replaces
the seed's k ``_decode`` calls with a host sync between each.
Verification + acceptance + commit are likewise one jitted call
(``_verify_accept_commit``): the per-slot Python acceptance loop is
replaced by a vectorized cumprod over the chain-match mask. Drafts never
write the real cache — only target verification does — so serving stays
lossless.

Fused tree drafting (DyTC §4.2, batched)
----------------------------------------
``tree_fused`` seeds every slot's tree with its PLD chain
(``core.tree.tree_seed_arrays``), then grows it on device with
``core.engine.tree_draft_scan``: one jitted ``lax.scan`` over expansion
steps, each re-decoding the padded (B, N) node block under per-slot dense
ancestor-closure masks, selecting the best P_acc leaf with ``jnp.argmax``
and appending TOP-P-filtered top-K children — Alg. 1 without host loops.
Per-slot expansion budgets come from the Eq. 5 objective
(``latency.best_tree_expansions`` over the slot's ``AcceptanceTracker``
alpha and the measured ``CostTracker`` cost), and trees are padded to a
fixed ``TREE_BUCKETS`` size so every round reuses one executable. The
verify half (``_tree_verify_accept_commit``) decodes the whole padded tree
once, walks the longest target-greedy path per slot with a vectorized tree
walk (``verify.greedy_accept_tree_batched``) and commits it — one drafting
dispatch + one verify dispatch per round, and greedy outputs stay
token-identical to AR decoding (drafts only change speed, never content).

Adaptive chain-cascade drafting (DyTC Eq. 5 analogue)
-----------------------------------------------------
Each slot carries an EMA acceptance estimate of its first NEURAL draft
token (Eq. 4, ``AcceptanceTracker`` keyed per slot; PLD outcomes are
excluded so the alpha prices the same drafter whose cost c is measured
from the neural scan) and the server maintains an online
draft-cost coefficient c = draft-token-latency / verify-round-latency
(``CostTracker``). Per round, each slot's draft length is the k maximizing
the chain EWIF T_SD(alpha_b, c, k) (``latency.best_chain_length``); a slot
whose best expected speedup falls below ``t_min`` stops neural drafting
(limit 0) and degrades to plain AR inside the same batched verify — the
chain analogue of DyTC's stop rule. PLD proposals are effectively free
(host-side retrieval, fixed-width verify), so they are never truncated by
the adaptive limit. Slot estimates reset on request admission (continuous
batching reuses slots across requests).

Dispatch contracts (PR 6)
-------------------------
``round_executables()`` enumerates every jitted executable a steady-state
round dispatches as ``{name: (jitted_fn, example_args)}``, and
``expected_dispatches_per_round()`` is the static count the runtime
``round_dispatches``/``host_syncs`` counters are held to.
``analysis.contracts.server_round_contracts`` lowers + compiles each
executable and asserts the discipline on the COMPILED artifact: donation
lowered to real ``input_output_alias`` entries, no host callbacks or
transfers inside a round body, the expected scan trip counts, and — on a
mesh — param/cache sharding annotations (``assert_sharding``) plus the
absence of resharding collectives. See docs/analysis.md.

Mesh-sharded serving (``mesh=``)
--------------------------------
Pass a ``("data", "model")`` mesh (``launch.mesh.make_mesh`` /
``mesh_from_spec``) and the server places the target AND every draft-bank
level tensor-parallel over ``model`` (``launch.sharding.param_specs``;
int8 bank copies inherit the target's placements) and shards the per-slot
round state — the KV cache, the carried ctx buffer, Eq. 4 EMAs, budgets —
over the data axes (``launch.sharding.cache_specs`` /
``round_state_specs``; batch stays replicated when ``max_batch`` doesn't
divide the data-way count). The fused rounds stay ONE donated dispatch on
the mesh: the engine pins carried state to its placement inside the round
(``core.engine._pin_batch``) and the server pins the jit boundary with
concrete ``NamedSharding`` out-constraints, so aliasing survives lowering
and no resharding collective runs between rounds. Greedy output is
token-identical to the single-device server in every mode
(tests/test_server_sharded.py). See docs/sharding.md.

Sampled serving (``sampling=``)
-------------------------------
Pass ``sampling=SamplingParams(temperature, top_k, top_p, seed)`` and every
mode verifies with the lossless stochastic accept/residual-resample rule
against the WARPED target distribution instead of greedy argmax — chain
rounds run the Leviathan accept, tree and cascade rounds the tree-native
walk, and cascades additionally use the stochastic level-to-level rescore
rule (core/verify.py, core/engine.py). The per-slot warp params and threefry
PRNG keys are carried device state (``dstate``), split in-dispatch, never
host-materialized, so sampling adds ZERO dispatches and ZERO host syncs to
any round shape; ``add_request(..., sampling=...)`` overrides params per
request. ``temperature=0`` requests stay token-identical to greedy, and a
greedy build (``sampling=None``) compiles byte-identical executables to
before sampling existed. See docs/serving.md.
"""
from __future__ import annotations

import functools
import os
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config.base import BlockKind, ModelConfig
from repro.core.acceptance import AcceptanceTracker, ema_init
from repro.core.dsia import DraftSpec, PLD_SPEC, build_hierarchy
from repro.core.engine import (
    cascade_rescore,
    cascade_rescore_verify,
    chain_draft_scan,
    chain_round,
    prefill_chunk_stage,
    tree_draft_scan,
    tree_round,
    tree_verify_accept_commit as _tree_verify_accept_commit,
    tree_verify_accept_commit_sampled as _tree_verify_accept_commit_sampled,
    verify_accept_commit as _verify_accept_commit,
    verify_accept_commit_sampled as _verify_accept_commit_sampled,
)
from repro.core.latency import (
    CostTracker,
    best_cascade_plan,
    best_chain_length,
    best_tree_expansions,
)
from repro.core.pld import PromptLookup
from repro.core.tree import bucket_for, tree_seed_arrays
from repro.core.verify import round_uniforms
from repro.models import model as M
from repro.serving import telemetry as TM
from repro.serving.draft_bank import DraftBank
from repro.serving.sampler import SamplingParams, warp_probs

PROPOSAL_MODES = ("chain_fused", "legacy", "tree_fused", "cascade_fused")
ROUND_MODES = ("auto", "single", "split")


def _prefill_bucket(n: int) -> int:
    """Padded admission-prefill length: next power of two >= n (floor 16).

    Bounds jit specializations of the B=1 prefill to O(log max_len) shapes
    while cutting its HBM and FLOPs to ~the prompt's size (satellite S1)."""
    b = 16
    while b < n:
        b *= 2
    return b


class BatchedSpecServer:
    def __init__(
        self,
        cfg: ModelConfig,
        params: dict,
        max_batch: int = 4,
        max_len: int = 1024,
        draft_k: int = 4,
        draft_spec: Optional[DraftSpec] = None,   # None -> PLD-only drafting
        fused: bool = True,            # False: seed-style per-step drafting (A/B)
        adaptive: bool = True,         # per-slot adaptive draft length
        t_min: float = 1.05,           # min expected speedup to keep drafting
        min_obs: int = 4,              # per-slot observations before adapting
        mode: Optional[str] = None,    # chain_fused | legacy | tree_fused | cascade_fused
        tree_expansions: int = 5,      # max tree expansion steps per round
        tree_top_k: int = 2,           # sibling candidates per expansion
        tree_top_p: float = 0.3,       # TOP-P sibling filter (P_tree)
        tree_bucket: Optional[int] = None,   # padded tree size (default: fit)
        attn_backend: Optional[str] = "auto",    # tree-verify staged pass
        hierarchy: Optional[List[DraftSpec]] = None,  # cascade_fused levels
        int8_exec: str = "auto",       # bank int8 path: auto | kernel | sim
        draft_kv: str = "auto",        # drafting scans: auto | carry | recompute
        round_mode: str = "auto",      # auto | single (one dispatch/round) | split
        sync_every: Optional[int] = None,   # single: drain every N rounds
        donate: Optional[bool] = None,      # None = auto (see below)
        mesh=None,                     # jax Mesh: TP params + DP slots (docstring)
        telemetry: bool = True,        # device-carried round telemetry buffer
        metrics: Optional[TM.MetricsRegistry] = None,   # shared host registry
        sampling: Optional[SamplingParams] = None,  # None -> greedy build
        paged: bool = False,           # block-paged KV cache (docs/paging.md)
        page_size: int = 64,           # tokens per KV page
        num_pages: Optional[int] = None,    # pool size (default: full per-slot)
        prefill_chunk: int = 0,        # >0: in-round chunked prefill (paged only)
    ):
        self.cfg, self.params = cfg, params
        self.B, self.max_len, self.k = max_batch, max_len, draft_k
        self.draft_spec = draft_spec
        # ---- block-paged KV cache + chunked prefill (docs/paging.md):
        # paged=True swaps the dense per-slot (B, max_len) attention buffers
        # for a shared page pool addressed through per-slot tables — BIT-
        # identical reads, so every mode below runs unchanged on it.
        # prefill_chunk>0 additionally makes admission enqueue-only: the
        # fused round dispatch itself consumes up to `prefill_chunk` prompt
        # tokens per slot per round (engine.prefill_chunk_stage), so a long
        # prompt never stalls the pipelined host loop.
        self.paged = bool(paged)
        self.page_size = int(page_size)
        self.prefill_chunk = int(prefill_chunk or 0)
        if self.prefill_chunk and not self.paged:
            raise ValueError(
                "prefill_chunk requires paged=True: chunked prompts commit "
                "through the page table, not a dense per-slot block"
            )
        # ---- sampled serving (module docstring): server-level defaults for
        # the per-slot warp params; per-request overrides ride admission.
        # A greedy build (None) compiles byte-identical executables to a
        # pre-sampling server — nothing below may branch on `sampling`
        # in a way that changes the greedy trace.
        self.sampling = sampling
        self._admit_seq = 0            # admissions so far (PRNG stream derivation)
        self._base_key = None
        if sampling is not None:
            self._base_key = jax.random.PRNGKey(
                sampling.seed if sampling.seed is not None else 0
            )
        # ---- mesh placement (tensor-parallel params, data-parallel slots).
        # Shardings are held per-server and applied with explicit
        # device_put / NamedSharding constraints — never via the global
        # mesh — so a sharded and a single-device server can coexist in
        # one process (the parity tests do exactly that).
        self.mesh = mesh
        self._param_sharding: Any = None       # NamedSharding trees when
        self._cache_sharding: Any = None       # mesh is set, else None
        self._c1_sharding: Any = None
        self._state_sharding: Any = None
        self._replicated: Any = None
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            from repro.launch import sharding as SH

            def ns_tree(spec_tree):
                return jax.tree.map(
                    lambda s: NamedSharding(mesh, s), spec_tree,
                    is_leaf=lambda x: isinstance(x, PartitionSpec),
                )

            self._param_sharding = ns_tree(SH.param_specs(cfg, mesh))
            self._cache_sharding = ns_tree(
                SH.cache_specs(cfg, mesh, global_batch=max_batch, paged=paged)
            )
            # the B=1 admission prefill cache is ALWAYS dense (write_slot
            # scatters it through the page table on paged builds)
            self._c1_sharding = ns_tree(
                SH.cache_specs(cfg, mesh, global_batch=1)
            )
            self._state_sharding = ns_tree(
                SH.round_state_specs(
                    mesh, global_batch=max_batch,
                    sampled=sampling is not None,
                    prefill=self.prefill_chunk > 0,
                )
            )
            self._replicated = NamedSharding(mesh, PartitionSpec())
            self.params = jax.device_put(self.params, self._param_sharding)
        if mode is None:
            mode = "chain_fused" if fused else "legacy"
        if mode not in PROPOSAL_MODES:
            raise ValueError(f"unknown proposal mode {mode!r}; pick one of {PROPOSAL_MODES}")
        if round_mode not in ROUND_MODES:
            raise ValueError(
                f"unknown round_mode {round_mode!r}; pick one of {ROUND_MODES}"
            )
        if round_mode == "auto":
            round_mode = "single" if mode in ("chain_fused", "tree_fused") else "split"
        if round_mode == "single" and mode not in ("chain_fused", "tree_fused"):
            raise ValueError(
                "round_mode='single' applies to chain_fused/tree_fused; "
                "legacy IS the per-step split baseline, and cascade_fused "
                "keeps one dispatch per level (the target verify rides the "
                "last rescore dispatch instead)"
            )
        self.round_mode = round_mode
        if sync_every is None:
            sync_every = int(os.environ.get("REPRO_SYNC_EVERY") or 1)
        self.sync_every = max(int(sync_every), 1)
        if donate is None:
            # donate on accelerators (aliasing the KV cache in place is the
            # HBM win); keep it OFF on CPU, where donating a buffer that an
            # in-flight round is still producing blocks the dispatching
            # thread until the producer finishes — serializing exactly the
            # async pipeline single mode exists for (measured ~3x round
            # slowdown in benchmarks/serve_batched.py's round arms)
            donate = jax.default_backend() != "cpu"
        self.donate = bool(donate)
        if draft_kv not in ("auto", "carry", "recompute"):
            raise ValueError(
                f"unknown draft_kv {draft_kv!r}; pick auto, carry or recompute"
            )
        attention_only = not cfg.num_codebooks and all(
            cfg.block_kind(i) is BlockKind.ATTENTION
            for i in range(cfg.num_layers)
        )
        if draft_kv == "auto":
            # carry: O(top_k) new-token decodes per expansion step instead of
            # the O(N) padded-block recompute — the win everywhere except SSM
            # stacks, whose per-step states cannot be carried row-wise
            draft_kv = "carry" if attention_only else "recompute"
        if draft_kv == "carry" and not attention_only:
            raise ValueError(
                "draft_kv='carry' requires an attention-only text stack "
                "(SSM per-step states are cumulative); use 'recompute'"
            )
        self.draft_kv = draft_kv
        if draft_spec is not None:
            if mode == "cascade_fused":
                raise ValueError(
                    "cascade_fused drafts from a hierarchy, not a single "
                    "draft_spec — pass hierarchy=[...] (or leave both unset "
                    "for the default mixing hierarchy)"
                )
            unsupported = draft_spec.unsupported_by_gates_only()
            if unsupported:
                raise ValueError(
                    f"mode {mode!r} drafts gates-only and cannot honor "
                    f"{', '.join(unsupported)} on draft_spec "
                    f"{draft_spec.name!r}; mode='cascade_fused' executes "
                    "quantize/attn_override levels through the draft bank"
                )
        if self.prefill_chunk:
            if self.round_mode != "single":
                raise ValueError(
                    "prefill_chunk rides the fused round dispatch — build "
                    "with round_mode='single' (chain_fused / tree_fused)"
                )
            if not attention_only:
                raise ValueError(
                    "prefill_chunk requires an attention-only text stack: "
                    "chunked prompt commits address KV through the page "
                    "table, and SSM per-step states are cumulative"
                )
        if hierarchy is not None and mode != "cascade_fused":
            raise ValueError("hierarchy=... requires mode='cascade_fused'")
        self.mode = mode
        self.fused = mode != "legacy"
        self.adaptive = adaptive
        self.t_min = t_min
        self.min_obs = min_obs
        self.tree_expansions = tree_expansions
        self.tree_top_k = tree_top_k
        self.tree_top_p = tree_top_p
        if attn_backend == "auto":
            # the Pallas kernel only beats the jnp dense pass when compiled
            # for real; off-TPU it would run in interpret mode (emulation)
            attn_backend = "pallas" if jax.default_backend() == "tpu" else None
        self.attn_backend = attn_backend
        self.tree_bucket = tree_bucket
        self.bank: Optional[DraftBank] = None
        if mode in ("tree_fused", "cascade_fused"):
            if cfg.num_codebooks or any(
                cfg.block_kind(i) is not BlockKind.ATTENTION
                for i in range(cfg.num_layers)
            ):
                raise ValueError(
                    f"{mode} requires an attention-only text stack: staged "
                    "SSM states are chain-ordered and cannot follow tree paths"
                )
            # worst case: root + PLD chain + top_k children per expansion
            # step (an explicit too-small tree_bucket is rejected by
            # tree_seed_arrays when the first round seeds the trees)
            extra = 0
            if mode == "cascade_fused":
                self.bank = DraftBank(
                    cfg, self.params,
                    hierarchy if hierarchy is not None
                    else build_hierarchy(cfg, "mixing"),
                    int8_exec=int8_exec,
                    param_sharding=self._param_sharding,
                )
                # one hedge sibling + one extension node per rescore level
                extra = 2 * len(self.bank.rescorers)
            self.tree_bucket = tree_bucket or bucket_for(
                1 + draft_k + tree_top_k * tree_expansions + extra
            )
        self.pld = PromptLookup(max_draft=draft_k)
        self.acceptance = AcceptanceTracker()
        self.costs = CostTracker()
        self.cache = M.init_cache(
            cfg, max_batch, max_len, dtype=jnp.dtype(cfg.dtype),
            paged=self.paged, page_size=self.page_size, num_pages=num_pages,
        )
        if mesh is not None:
            self.cache = jax.device_put(self.cache, self._cache_sharding)
        # host-side page allocator (paged builds): a plain free list touched
        # only at admission/retire — both existing sync points — so the
        # steady-state rounds never see an allocation decision
        self._pages_per_slot = 0
        self._free_pages: List[int] = []
        self._slot_pages: Dict[int, List[int]] = {}
        if self.paged:
            self._pages_per_slot = M.pages_for(max_len, self.page_size)
            pool = (
                int(num_pages) if num_pages is not None
                else max_batch * self._pages_per_slot
            )
            # pop() from the end -> lowest page indices hand out first
            self._free_pages = list(range(pool))[::-1]
        self.pending = np.zeros(max_batch, np.int64)
        self.contexts: List[List[int]] = [[] for _ in range(max_batch)]
        self.live = np.zeros(max_batch, bool)
        self._pld_have = np.zeros(max_batch, np.int32)   # PLD prefix per round

        # device-resident round state (single mode): the carried arrays the
        # fused round reads AND maintains — pending/live, the PLD context
        # buffer, and the per-slot Eq. 4 estimator (see acceptance.ema_init)
        prior0 = float(draft_spec.prior_alpha) if draft_spec else 0.5
        al0, h0, hn0, hp0 = ema_init(max_batch, prior=prior0)
        self.dstate = {
            "pending": jnp.zeros((max_batch,), jnp.int32),
            "live": jnp.zeros((max_batch,), bool),
            "ctx": jnp.zeros((max_batch, max_len), jnp.int32),
            "alpha": al0, "hist": h0, "hist_n": hn0, "hist_ptr": hp0,
        }
        if sampling is not None:
            # per-slot sampling state carried INSIDE the fused rounds: warp
            # params and the threefry keys the dispatches split themselves
            self.dstate.update(
                temp=jnp.zeros((max_batch,), jnp.float32),
                topk=jnp.zeros((max_batch,), jnp.int32),
                topp=jnp.ones((max_batch,), jnp.float32),
                key=jnp.zeros((max_batch, 2), jnp.uint32),
            )
        if self.prefill_chunk:
            # chunked-prefill progress per slot: prompt tokens committed so
            # far vs prompt length; a slot with pf_done < pf_len is masked
            # dead for the decode half of the round (it is still prefilling)
            self.dstate.update(
                pf_done=jnp.zeros((max_batch,), jnp.int32),
                pf_len=jnp.zeros((max_batch,), jnp.int32),
            )
        if mesh is not None:
            self.dstate = jax.device_put(self.dstate, self._state_sharding)
        self._prior_alpha = prior0
        c0 = float(draft_spec.prior_c) if draft_spec else 0.5
        self._c_dev = jnp.asarray(max(c0, 1e-3), jnp.float32)
        if mesh is not None:
            self._c_dev = jax.device_put(self._c_dev, self._replicated)
        self._inflight: List[dict] = []     # undrained round outputs (single)
        self._out_buf: Dict[int, List[int]] = {}
        self._last_limit = np.zeros(max_batch, np.int32)   # split-round budgets

        # ---- telemetry (docs/observability.md): the host registry is
        # ALWAYS on (it backs .stats, so existing counter reads cost what
        # they always did); ``telemetry=`` gates only the device-carried
        # round buffer, which single-mode rounds accumulate inside THE
        # round dispatch and host-synced rounds mirror into a numpy twin.
        # Drains happen exclusively at existing sync points (flush /
        # admission), so round_dispatches/host_syncs stay bit-identical.
        self.telemetry = bool(telemetry)
        self.metrics = metrics if metrics is not None else TM.MetricsRegistry()
        budget_max = self.k if mode in ("chain_fused", "legacy") else tree_expansions
        self._telem_schema = TM.telemetry_schema(
            max_batch, budget_max,
            levels=len(self.bank) if self.bank is not None else 0,
        )
        self._telem_host = TM.init_host_telemetry(self._telem_schema)
        self._telem_seen = TM.init_host_telemetry(self._telem_schema)
        self._telem_dev = None
        self._telem_sharding = None
        if self.telemetry:
            self._telem_dev = TM.init_device_telemetry(self._telem_schema)
            if mesh is not None:
                # per-slot tallies are pure data parallelism, like dstate
                self._telem_sharding = ns_tree(SH.telemetry_specs(
                    self._telem_schema, mesh, global_batch=max_batch
                ))
                self._telem_dev = jax.device_put(
                    self._telem_dev, self._telem_sharding
                )

        don = lambda *idx: idx if self.donate else ()   # noqa: E731
        # admission: the fresh B=1 cache is donated into the prefill, and
        # the batched cache is donated into the jitted slot write — no host
        # round trip, no full-cache copy
        self._prefill1 = jax.jit(
            lambda p, b, c: M.prefill(cfg, p, b, c), donate_argnums=don(2)
        )
        if self.paged:
            # paged admission: bind the slot's page-table row, then scatter
            # the (dense, bucketed) B=1 prefill cache through it — one
            # jitted dispatch, same as the dense write
            def _wslot_paged(cache, c1, slot, table_row):
                cache = dict(
                    cache,
                    page_table=cache["page_table"].at[slot].set(table_row),
                )
                return M.write_slot(cfg, cache, c1, slot)

            self._write_slot_fn = jax.jit(_wslot_paged, donate_argnums=don(0))
        else:
            self._write_slot_fn = jax.jit(
                functools.partial(M.write_slot, cfg), donate_argnums=don(0)
            )

        def _admit(state, slot, ctx_row, last_logits, *samp):
            prior = jnp.float32(self._prior_alpha)
            W = state["hist"].shape[1]
            out = {
                "pending": state["pending"].at[slot].set(
                    jnp.argmax(last_logits[0], -1).astype(jnp.int32)
                ),
                "live": state["live"].at[slot].set(True),
                "ctx": state["ctx"].at[slot].set(ctx_row),
                "alpha": state["alpha"].at[slot].set(prior),
                "hist": state["hist"].at[slot].set(jnp.zeros((W,), jnp.float32)),
                "hist_n": state["hist_n"].at[slot].set(0),
                "hist_ptr": state["hist_ptr"].at[slot].set(0),
            }
            if samp:
                # sampled build: bind the request's (host-sampled) first
                # token, warp params and PRNG key row to the slot
                pend0, temp, topk, topp, key_row = samp
                out["pending"] = state["pending"].at[slot].set(pend0)
                out["temp"] = state["temp"].at[slot].set(temp)
                out["topk"] = state["topk"].at[slot].set(topk)
                out["topp"] = state["topp"].at[slot].set(topp)
                out["key"] = state["key"].at[slot].set(key_row)
            return out

        self._admit_fn = jax.jit(_admit, donate_argnums=don(0))

        self._admit_pf_fn = None
        if self.prefill_chunk:
            # enqueue-only admission: bind the table row, zero the slot's
            # position, park the prompt in the ctx row and arm the pf_*
            # counters — NO prefill dispatch, no B=1 cache, no model FLOPs;
            # the next fused round starts consuming the prompt in chunks
            def _admit_pf(cache, state, slot, ctx_row, pf_len, table_row,
                          *samp):
                cache = dict(
                    cache,
                    page_table=cache["page_table"].at[slot].set(table_row),
                    pos=cache["pos"].at[slot].set(0),
                )
                prior = jnp.float32(self._prior_alpha)
                W = state["hist"].shape[1]
                out = dict(
                    state,
                    # ctx_row[0] is a "safe" pending: the round prologue
                    # scatters pending at ctx[pos] for EVERY slot, so for a
                    # mid-prefill slot it must be a value no-op on the
                    # prompt (prefill_chunk_stage keeps the invariant)
                    pending=state["pending"].at[slot].set(ctx_row[0]),
                    live=state["live"].at[slot].set(True),
                    ctx=state["ctx"].at[slot].set(ctx_row),
                    alpha=state["alpha"].at[slot].set(prior),
                    hist=state["hist"].at[slot].set(
                        jnp.zeros((W,), jnp.float32)
                    ),
                    hist_n=state["hist_n"].at[slot].set(0),
                    hist_ptr=state["hist_ptr"].at[slot].set(0),
                    pf_done=state["pf_done"].at[slot].set(0),
                    pf_len=state["pf_len"].at[slot].set(pf_len),
                )
                if samp:
                    temp, topk, topp, key_row = samp
                    out["temp"] = state["temp"].at[slot].set(temp)
                    out["topk"] = state["topk"].at[slot].set(topk)
                    out["topp"] = state["topp"].at[slot].set(topp)
                    # the UNSPLIT request key: prefill_chunk_stage splits
                    # it when the prompt completes, reproducing the dense
                    # path's host-side admission split bit-for-bit
                    out["key"] = state["key"].at[slot].set(key_row)
                return cache, out

            self._admit_pf_fn = jax.jit(_admit_pf, donate_argnums=don(0, 1))

        # legacy (unfused) drafting path — kept for A/B benchmarking
        self._decode = jax.jit(
            lambda p, c, t, g: M.decode_step(cfg, p, c, t, gates=g)
        )
        self._verify = jax.jit(
            functools.partial(_verify_accept_commit, cfg), donate_argnums=don(1)
        )
        self._tree_verify = jax.jit(functools.partial(
            _tree_verify_accept_commit, cfg, attn_backend=attn_backend,
        ), donate_argnums=don(1))
        self._verify_sampled = None
        self._tree_verify_sampled = None
        if sampling is not None:
            # split/legacy verify with the stochastic accept fused in: the
            # slot keys are split into the round uniforms INSIDE the jitted
            # dispatch and the advanced keys return as device arrays — the
            # split round keeps its dispatch/sync counts exactly
            def _sverify(p, cache, pending, chains, have, live,
                         temp, topk, topp, key):
                key, u = round_uniforms(key, draft_k + 1)
                cache, n_chain, nxt = _verify_accept_commit_sampled(
                    cfg, p, cache, pending, chains, have, live,
                    temp, topk, topp, u,
                )
                return cache, n_chain, nxt, key

            self._verify_sampled = jax.jit(_sverify, donate_argnums=don(1))
            if self.tree_bucket:
                bucket = int(self.tree_bucket)

                def _stree_verify(p, cache, tok, par, dep, msk, cnt, live,
                                  temp, topk, topp, key):
                    key, u = round_uniforms(key, bucket)
                    cache, path, n_acc, bonus = (
                        _tree_verify_accept_commit_sampled(
                            cfg, p, cache, tok, par, dep, msk, cnt, live,
                            temp, topk, topp, u, attn_backend=attn_backend,
                        )
                    )
                    return cache, path, n_acc, bonus, key

                self._tree_verify_sampled = jax.jit(
                    _stree_verify, donate_argnums=don(1)
                )
        self._round_fn = None
        if self.round_mode == "single":
            pld_kw = {
                "max_ngram": self.pld.max_ngram, "min_ngram": self.pld.min_ngram,
            }
            # `sampled=True` is only passed on sampled builds so a greedy
            # build's round partial (and its trace) stays byte-identical
            samp_kw = {"sampled": True} if sampling is not None else {}
            if mode == "chain_fused":
                fn = functools.partial(
                    chain_round, cfg, draft_k=draft_k,
                    use_draft=draft_spec is not None, adaptive=adaptive,
                    min_obs=min_obs, t_min=float(t_min),
                    draft_kv=self.draft_kv, **pld_kw, **samp_kw,
                )
            else:
                fn = functools.partial(
                    tree_round, cfg, draft_k=draft_k,
                    expansions=tree_expansions, top_k=tree_top_k,
                    top_p=tree_top_p, bucket=self.tree_bucket,
                    pld_alpha=float(PLD_SPEC.prior_alpha),
                    use_draft=draft_spec is not None, adaptive=adaptive,
                    min_obs=min_obs, t_min=float(t_min),
                    draft_kv=self.draft_kv, attn_backend=attn_backend,
                    **pld_kw, **samp_kw,
                )
            if mesh is not None:
                # belt-and-braces on a mesh: pin the donated outputs to the
                # exact input placements at the jit boundary (concrete
                # NamedShardings work on every supported JAX, unlike the
                # abstract-mesh form), so the cache/state aliasing can never
                # be dropped by an output-sharding drift — the single
                # dispatch stays resharding-free between rounds
                inner_round = fn
                csh, ssh = self._cache_sharding, self._state_sharding

                def fn(p, cache, state, c, gates):
                    cache, state, out = inner_round(p, cache, state, c, gates)
                    cache = jax.tree.map(
                        jax.lax.with_sharding_constraint, cache, csh
                    )
                    state = jax.tree.map(
                        jax.lax.with_sharding_constraint, state, ssh
                    )
                    return cache, state, out

            # donate the cache AND the carried state: the commit scatter and
            # the state updates alias in place instead of copying the
            # largest live buffers every round
            if self.telemetry:
                # compose the telemetry accumulation INTO the round at the
                # jit boundary: the buffer rides the same dispatch (and the
                # same donation) as the cache/state, so the round stays ONE
                # dispatch with zero host syncs — proven on the compiled
                # HLO against the telemetry-off executable by
                # analysis.contracts.assert_telemetry_transparent
                inner_fn = fn
                tsh = self._telem_sharding

                def fn_t(p, cache, state, telem, c, gates):
                    live = state["live"]
                    cache, state, out = inner_fn(p, cache, state, c, gates)
                    telem = TM.accumulate_round(telem, out, live)
                    if tsh is not None:
                        telem = jax.tree.map(
                            jax.lax.with_sharding_constraint, telem, tsh
                        )
                    return cache, state, telem, out

                round_core, round_don = fn_t, don(1, 2, 3)
            else:
                round_core, round_don = fn, don(1, 2)
            if self.prefill_chunk:
                # chunked prefill rides the SAME dispatch, outermost: first
                # consume up to `prefill_chunk` pending prompt tokens per
                # slot, then run the decode round with mid-prefill slots
                # masked dead — the speculative machinery skips them and
                # telemetry credits them no decode rounds. Their real live
                # bit is restored on the way out.
                inner_core = round_core
                chunk = int(self.prefill_chunk)
                pf_sampled = sampling is not None

                def round_core(p, cache, state, *rest):
                    with jax.named_scope("prefill"):
                        cache, state = prefill_chunk_stage(
                            cfg, p, cache, state, chunk=chunk,
                            sampled=pf_sampled,
                        )
                    live0 = state["live"]
                    state = dict(
                        state,
                        live=live0 & (state["pf_done"] >= state["pf_len"]),
                    )
                    outs = inner_core(p, cache, state, *rest)
                    state2 = dict(outs[1], live=live0)
                    return (outs[0], state2) + tuple(outs[2:])

            self._round_fn = jax.jit(round_core, donate_argnums=round_don)
        self._rescore_verify_fns: Dict[int, Callable] = {}
        self._draft_fns: Dict[int, Callable] = {}   # scan steps -> jitted fn
        self._tree_draft_fns: Dict[int, Callable] = {}   # expansions -> jitted fn
        self._casc_draft_fns: Dict[int, Callable] = {}   # expansions -> jitted fn
        self._rescore_fns: Dict[int, Callable] = {}      # level index -> jitted fn
        self._gates = (
            None
            if draft_spec is None
            else jnp.asarray(draft_spec.gates_array(cfg.num_layers))
        )
        if mesh is not None and self._gates is not None:
            self._gates = jax.device_put(self._gates, self._replicated)
        self._level_gates: Dict[int, Optional[jax.Array]] = {}
        if self.bank is not None:
            for lvl in self.bank.levels:
                g = None if lvl.gates is None else jnp.asarray(lvl.gates)
                if mesh is not None and g is not None:
                    g = jax.device_put(g, self._replicated)
                self._level_gates[lvl.index] = g
        # the legacy stats facade: same keys (incl. the round-pipeline
        # accounting — jitted dispatches per round, block_until_ready
        # events, host wall time blocked on device results), same integer
        # semantics, now backed by registry counters (telemetry
        # .STATS_METRICS) so pinned test reads and the /metrics endpoint
        # can never drift apart
        self.stats: TM.StatsView = TM.StatsView(self.metrics)
        # host phase spans (telemetry.PHASES): serve.round around every
        # step, and inside it plan / dispatch / wait / commit / drain
        self._span = TM.PhaseSpans(self.metrics)

    # ------------------------------------------------------------ admission
    def add_request(
        self, slot: int, prompt: np.ndarray,
        sampling: Optional[SamplingParams] = None,
        max_new_tokens: Optional[int] = None,
    ) -> None:
        """Prefill one prompt into a batch slot.

        ``max_new_tokens`` (paged builds) bounds the slot's KV page
        allocation to prompt + budget + round slack instead of the full
        ``max_len`` reservation — the HBM win paging exists for; dense
        builds ignore it. On ``prefill_chunk`` builds admission is
        ENQUEUE-ONLY: no prefill dispatch runs here at all — the prompt is
        parked in the slot's context row and the next fused round starts
        consuming it ``prefill_chunk`` tokens at a time alongside the
        decoding slots (docs/paging.md).

        ``sampling`` overrides the server build's default ``SamplingParams``
        for this request (sampled builds only — a stochastic request on a
        greedy build raises, since the greedy executables cannot honor it;
        ``temperature=0`` overrides are accepted anywhere and stay
        token-identical to greedy). On sampled builds the request's FIRST
        token is drawn host-side from the warped prefill distribution
        (admission is already a sync point) and its slot PRNG stream is
        seeded from ``sampling.seed`` or derived from the server's base
        seed and the admission counter.

        The fresh B=1 cache is donated into the prefill dispatch and the
        batched cache into one jitted dynamic-update (``models.model
        .write_slot``) — admission never round-trips cache buffers through
        the host. In pipelined single mode, any in-flight rounds are drained
        first (sync-on-admit) and whatever the RE-BOUND slot had buffered is
        discarded: those tokens belong to the previous request and can no
        longer be attributed once the slot is re-bound. Call ``flush()``
        before re-binding to collect them — ``ServeLoop`` drains and routes
        under the old mapping before every admission, so it never loses
        any."""
        if (sampling is not None and not sampling.greedy
                and self.sampling is None):
            raise ValueError(
                "stochastic per-request sampling requires a sampled server "
                "build — construct BatchedSpecServer(..., sampling="
                "SamplingParams(...)); this greedy build compiled only the "
                "greedy round executables"
            )
        if self._inflight:
            self._drain()
        dropped = self._out_buf.pop(slot, None)
        if dropped:
            # tokens committed for the PREVIOUS binding of this slot that
            # no caller collected before re-binding: counted so drained
            # telemetry reconciles exactly with routed request streams
            # (tests/test_telemetry.py)
            self.metrics.counter("serve_discarded_tokens_total").inc(
                len(dropped)
            )
        prompt = np.asarray(prompt, np.int32)
        table_row = None
        if self.paged:
            alloc = (
                self.max_len if max_new_tokens is None
                else min(
                    self.max_len,
                    len(prompt) + int(max_new_tokens) + self._alloc_slack(),
                )
            )
            table_row = self._alloc_pages(slot, alloc)
        if self.prefill_chunk:
            self._admit_chunked(slot, prompt, table_row, sampling)
            return
        # admission prefill at the prompt's padded power-of-two bucket, not
        # max_len — write_slot places the short cache into the batched one
        # (dense: dynamic_update_slice; paged: table scatter) and positions
        # past the prompt stay invisible via kv_pos masking
        bucket = min(_prefill_bucket(len(prompt)), self.max_len)
        c1 = M.init_cache(self.cfg, 1, bucket, dtype=jnp.dtype(self.cfg.dtype))
        if self.mesh is not None:
            # B=1 prefill cache: batch can't shard, but layout must match the
            # sharded weights it is written from (TP head placement)
            c1 = jax.device_put(c1, self._c1_sharding)
        with self._span("serve.dispatch"):
            last, c1 = self._prefill1(
                self.params, {"tokens": jnp.asarray(prompt[None])}, c1
            )
            slot_d = jnp.asarray(slot, jnp.int32)
            if self.paged:
                self.cache = self._write_slot_fn(
                    self.cache, c1, slot_d, jnp.asarray(table_row)
                )
            else:
                self.cache = self._write_slot_fn(self.cache, c1, slot_d)
        # the first token is read from the prefill's logits below: the
        # block on them is device time (``device_wait``), not host time.
        # Not a round's sync, so ``host_syncs`` does not count it.
        with self._span("serve.wait"):
            last = jax.block_until_ready(last)
        # device round state: pending/live/context row + a fresh Eq. 4
        # estimator seeded with the draft's cold-start prior
        row = np.zeros(self.max_len, np.int32)
        row[: len(prompt)] = prompt
        samp_args = ()
        first: Optional[int] = None
        if self.sampling is not None:
            eff = sampling if sampling is not None else self.sampling
            if eff.seed is not None:
                key = jax.random.PRNGKey(eff.seed)
            else:
                key = jax.random.fold_in(self._base_key, self._admit_seq)
            self._admit_seq += 1
            # the request's FIRST token is sampled from the warped prefill
            # distribution right here — admission is already a host sync
            # point — with the same inverse-CDF rule the device uses; the
            # consumed subkey advances the slot stream like a round split
            key, sub = jax.random.split(key)
            u0 = float(jax.random.uniform(sub))
            q0 = warp_probs(
                np.asarray(last)[0], eff.temperature, eff.top_k, eff.top_p
            )
            cum = np.cumsum(q0)
            first = int(np.argmax(cum > u0 * cum[-1]))
            samp_args = (
                jnp.asarray(first, jnp.int32),
                jnp.asarray(max(eff.temperature, 0.0), jnp.float32),
                jnp.asarray(eff.top_k, jnp.int32),
                jnp.asarray(eff.top_p, jnp.float32),
                key,
            )
            if not eff.greedy:
                self.metrics.counter("serve_sampled_requests_total").inc()
        self.dstate = self._admit_fn(
            self.dstate, slot_d, jnp.asarray(row), last, *samp_args
        )
        # host mirrors (split/legacy/cascade rounds + inspection)
        self.pending[slot] = (
            int(np.argmax(np.asarray(last)[0])) if first is None else first
        )
        self.contexts[slot] = [int(t) for t in prompt]
        self.live[slot] = True
        # slot estimators restart with the draft's cold-start prior —
        # continuous batching reuses slots across unrelated requests
        prior = self.draft_spec.prior_alpha if self.draft_spec else 0.5
        self.acceptance.reset(self._slot_key(slot), alpha0=prior)
        if self.bank is not None:
            for i in range(len(self.bank)):
                self.acceptance.reset(
                    self.bank.slot_key(i, slot), alpha0=self.bank.alpha_prior(i)
                )
            self.acceptance.reset(
                self.bank.direct_key(slot), alpha0=self.bank.direct_prior()
            )

    def _admit_chunked(
        self, slot: int, prompt: np.ndarray,
        table_row: np.ndarray, sampling: Optional[SamplingParams],
    ) -> None:
        """Enqueue-only admission (``prefill_chunk`` builds): one jitted
        state/table bind and the host loop moves on — the prompt prefills
        inside the next fused round dispatches."""
        row = np.zeros(self.max_len, np.int32)
        row[: len(prompt)] = prompt
        samp_args = ()
        if self.sampling is not None:
            eff = sampling if sampling is not None else self.sampling
            if eff.seed is not None:
                key = jax.random.PRNGKey(eff.seed)
            else:
                key = jax.random.fold_in(self._base_key, self._admit_seq)
            self._admit_seq += 1
            samp_args = (
                jnp.asarray(max(eff.temperature, 0.0), jnp.float32),
                jnp.asarray(eff.top_k, jnp.int32),
                jnp.asarray(eff.top_p, jnp.float32),
                key,    # unsplit: the completing round splits it in-dispatch
            )
            if not eff.greedy:
                self.metrics.counter("serve_sampled_requests_total").inc()
        slot_d = jnp.asarray(slot, jnp.int32)
        self.cache, self.dstate = self._admit_pf_fn(
            self.cache, self.dstate, slot_d, jnp.asarray(row),
            jnp.asarray(len(prompt), jnp.int32), jnp.asarray(table_row),
            *samp_args,
        )
        # host mirrors: pending is unknown until the prompt finishes
        # prefilling in-round; chunked builds are single-mode only, so the
        # mirror is purely informational
        self.pending[slot] = int(prompt[-1])
        self.contexts[slot] = [int(t) for t in prompt]
        self.live[slot] = True
        prior = self.draft_spec.prior_alpha if self.draft_spec else 0.5
        self.acceptance.reset(self._slot_key(slot), alpha0=prior)

    # -------------------------------------------------- page pool (paged)
    def _alloc_slack(self) -> int:
        """Worst-case commit overshoot past ``max_new_tokens``: pipelined
        rounds in flight when the finish is observed keep committing."""
        per_round = self.tree_bucket or (self.k + 1)
        return (self.sync_every + 1) * per_round

    def _alloc_pages(self, slot: int, n_tokens: int) -> np.ndarray:
        """Reserve pool pages covering ``n_tokens`` for a slot; returns the
        slot's full table row (-1 padded past the allocation)."""
        need = min(
            -(-int(n_tokens) // self.page_size), self._pages_per_slot
        )
        self._free_slot_pages(slot)
        if need > len(self._free_pages):
            raise RuntimeError(
                f"KV page pool exhausted: slot {slot} needs {need} pages, "
                f"{len(self._free_pages)} free — raise num_pages or admit "
                "fewer/shorter concurrent requests"
            )
        pages = [self._free_pages.pop() for _ in range(need)]
        self._slot_pages[slot] = pages
        self.metrics.gauge("serve_free_pages").set(len(self._free_pages))
        row = np.full(self._pages_per_slot, -1, np.int32)
        row[:need] = pages
        return row

    def _free_slot_pages(self, slot: int) -> None:
        pages = self._slot_pages.pop(slot, None)
        if pages:
            self._free_pages.extend(pages)
            self.metrics.gauge("serve_free_pages").set(len(self._free_pages))

    def release(self, slot: int) -> None:
        """Mark a slot free (its request finished or was cancelled)."""
        self.live[slot] = False
        upd = dict(
            self.dstate, live=self.dstate["live"].at[slot].set(False)
        )
        if self.prefill_chunk:
            # a request cancelled mid-prefill must stop consuming chunks
            upd["pf_len"] = self.dstate["pf_len"].at[slot].set(0)
            upd["pf_done"] = self.dstate["pf_done"].at[slot].set(0)
        self.dstate = upd
        if self.paged:
            # host-side free at an existing sync point; the stale device
            # table row is harmless (a dead slot never commits — its writes
            # carry the out-of-pool sentinel page) and the row is re-bound
            # at the slot's next admission
            self._free_slot_pages(slot)

    def _slot_key(self, slot: int) -> str:
        return f"chain:{slot}"

    # ----------------------------------------------------- adaptive lengths
    def _slot_limit(self, slot: int) -> int:
        """Neural draft budget for a slot this round (PLD is never capped).

        In single round mode this is an inspection mirror of the on-device
        Eq. 5 selection (the round computes budgets from the carried state
        arrays itself); split rounds compute it here from the host trackers."""
        if self.draft_spec is None:
            return 0
        if self.round_mode == "single":
            if not self.adaptive or int(self.dstate["hist_n"][slot]) < self.min_obs:
                return self.k
            alpha = float(self.dstate["alpha"][slot])
            return best_chain_length(
                alpha, float(self._c_dev), self.k, self.t_min
            )
        key = self._slot_key(slot)
        if not self.adaptive or self.acceptance.counts(key) < self.min_obs:
            return self.k
        alpha = self.acceptance.alpha(key)
        c = self.costs.c_hat(
            "chain_draft", default=float(self.draft_spec.prior_c)
        )
        return best_chain_length(alpha, max(c, 1e-3), self.k, self.t_min)

    def _slot_tree_budget(self, slot: int) -> int:
        """Tree expansion budget for a slot this round (Eq. 5 objective).
        Single round mode: inspection mirror of the on-device selection."""
        if self.draft_spec is None:
            return 0
        if self.round_mode == "single":
            if not self.adaptive or int(self.dstate["hist_n"][slot]) < self.min_obs:
                return self.tree_expansions
            alpha = float(self.dstate["alpha"][slot])
            return best_tree_expansions(
                alpha, float(self._c_dev), self.tree_expansions, self.t_min
            )
        key = self._slot_key(slot)
        if not self.adaptive or self.acceptance.counts(key) < self.min_obs:
            return self.tree_expansions
        alpha = self.acceptance.alpha(key)
        c = self.costs.c_hat(
            "tree_draft", default=float(self.draft_spec.prior_c)
        )
        return best_tree_expansions(
            alpha, max(c, 1e-3), self.tree_expansions, self.t_min
        )

    def _draft_fn(self, steps: int):
        fn = self._draft_fns.get(steps)
        if fn is None:
            fn = jax.jit(functools.partial(
                chain_draft_scan, self.cfg, steps, draft_kv=self.draft_kv,
            ))
            self._draft_fns[steps] = fn
        return fn

    def _tree_draft_fn(self, expansions: int):
        fn = self._tree_draft_fns.get(expansions)
        if fn is None:
            fn = jax.jit(functools.partial(
                tree_draft_scan, self.cfg, expansions, self.tree_top_k,
                top_p=self.tree_top_p, draft_kv=self.draft_kv,
            ))
            self._tree_draft_fns[expansions] = fn
        return fn

    def _casc_draft_fn(self, expansions: int):
        """The cascade's drafting scan: ``tree_draft_scan`` bound to the
        CHEAPEST bank level's static execution (quantize/attn_override);
        its params/gates arrive as call arguments."""
        fn = self._casc_draft_fns.get(expansions)
        if fn is None:
            drafter = self.bank.drafter
            fn = jax.jit(functools.partial(
                tree_draft_scan, self.cfg, expansions, self.tree_top_k,
                top_p=self.tree_top_p, quantize=drafter.quantize,
                attn_override=drafter.attn_override, draft_kv=self.draft_kv,
            ))
            self._casc_draft_fns[expansions] = fn
        return fn

    def _rescore_fn(self, level: int):
        """One jitted intermediate-verify dispatch for bank level
        ``level`` (Alg. 1 level-to-level acceptance)."""
        fn = self._rescore_fns.get(level)
        if fn is None:
            lvl = self.bank.levels[level]
            base = functools.partial(
                cascade_rescore, self.cfg, quantize=lvl.quantize,
                attn_override=lvl.attn_override,
                attn_backend=self.attn_backend,
            )
            if self.sampling is not None:
                inner = base

                def base(lp, cache, tk, pr, dp, pa, mk, ct, probe, apply,
                         alphas, gates, temp, topk, topp, key):
                    # stochastic level-to-level rescore: the slot keys split
                    # in-dispatch into the N endorse draws + hedge +
                    # extension uniforms; advanced keys come back last
                    key, u = round_uniforms(key, tk.shape[1] + 2)
                    out = inner(lp, cache, tk, pr, dp, pa, mk, ct, probe,
                                apply, alphas, gates,
                                sampling=(temp, topk, topp, u))
                    return out + (key,)

            fn = jax.jit(base)
            self._rescore_fns[level] = fn
        return fn

    def _rescore_verify_fn(self, level: int):
        """The LAST rescore dispatch with the target verify folded in
        (``core.engine.cascade_rescore_verify``): the strongest level's
        intermediate verify and the target's verify + commit ride one
        jitted call, with the cache donated so the commit aliases in
        place — an L-level cascade round stays at L dispatches."""
        fn = self._rescore_verify_fns.get(level)
        if fn is None:
            lvl = self.bank.levels[level]
            base = functools.partial(
                cascade_rescore_verify, self.cfg, quantize=lvl.quantize,
                attn_override=lvl.attn_override,
                attn_backend=self.attn_backend,
            )
            if self.sampling is not None:
                # forward the trailing (temp, top_k, top_p, key) as the
                # fused call's sampling tuple; the keys split in-dispatch
                # (2N+2 uniforms: stochastic rescore + stochastic walk) and
                # the 13-tuple grows a trailing new_key output
                inner_rv = base

                def base(lp, p, cache, tk, pr, dp, pa, mk, ct, probe, apply,
                         alphas, gates, live, temp, topk, topp, key):
                    return inner_rv(lp, p, cache, tk, pr, dp, pa, mk, ct,
                                    probe, apply, alphas, gates, live,
                                    sampling=(temp, topk, topp, key))
            if self.telemetry:
                # the telemetry buffer rides the cascade's FINAL (donated)
                # dispatch: the per-slot tallies, routing rows, and THIS
                # dispatch's Eq. 4 verdict (level ``index + 1``'s first
                # token) accumulate inside the same executable — the
                # bounded L-dispatch round stays L dispatches. Verdicts of
                # intermediate rescorers and of the target (row 0) are
                # host-mirrored by _step_cascade from arrays it already
                # materializes.
                bank = self.bank
                rescorer_rows = tuple(lv.index for lv in bank.rescorers)
                drafter_row = bank.drafter.index
                obs_row = lvl.index + 1
                tsh = self._telem_sharding

                def wrapped(lp, p, cache, tk, pr, dp, pa, mk, ct, probe,
                            apply, alphas, gates, live, telem, pld_have,
                            budget, *samp):
                    # *samp = (temp, topk, topp, key) on sampled builds —
                    # appended after the telemetry args so the greedy
                    # signature (and its trace) is untouched
                    out = base(lp, p, cache, tk, pr, dp, pa, mk, ct, probe,
                               apply, alphas, gates, live, *samp)
                    # out[5]=count, out[7]=probe_ok, out[8]=probe_valid,
                    # out[11]=n_acc (see cascade_rescore_verify)
                    telem = TM.accumulate_cascade(
                        telem, live=live, n_acc=out[11], count=out[5],
                        pld_have=pld_have, budget=budget, routed=apply,
                        probe_ok=out[7], probe_valid=out[8],
                        rescorer_rows=rescorer_rows,
                        drafter_row=drafter_row, obs_row=obs_row,
                    )
                    if tsh is not None:
                        telem = jax.tree.map(
                            jax.lax.with_sharding_constraint, telem, tsh
                        )
                    return out + (telem,)

                fn = jax.jit(
                    wrapped, donate_argnums=(2, 14) if self.donate else ()
                )
            else:
                fn = jax.jit(base, donate_argnums=(2,) if self.donate else ())
            self._rescore_verify_fns[level] = fn
        return fn

    # ------------------------------------------------- dispatch contracts
    def expected_dispatches_per_round(self) -> int:
        """Jitted dispatches a fully-drafting steady-state round performs —
        the static claim the runtime ``round_dispatches``/
        ``draft_dispatches``/``rescore_dispatches`` counters and the
        compiled contracts (``analysis.contracts``) are both held to.

        single:  1 (THE fused round executable)
        split:   2 (draft scan + verify), 1 with no neural drafter
        legacy:  draft_k decode dispatches + 1 verify
        cascade: L = 1 drafting scan + (L-1) rescores, target verify folded
                 into the last rescore (the paper's <= L+1 bound, met with
                 room to spare); a 1-level bank is drafting scan + verify.
        """
        if self.round_mode == "single":
            return 1
        if self.mode == "legacy":
            return (self.k if self.draft_spec is not None else 0) + 1
        if self.mode == "cascade_fused":
            return max(len(self.bank), 2)
        return 2 if self.draft_spec is not None else 1

    def round_executables(self) -> Dict[str, Tuple[Callable, tuple]]:
        """Every jitted executable a steady-state round dispatches, as
        ``{name: (jitted_fn, example_args)}`` ready for ``.lower()`` —
        the input ``analysis.contracts.server_round_contracts`` compiles
        and checks. Example args mirror the live call sites (lowering never
        executes, so handing over donated buffers is safe)."""
        B, k = self.B, self.k
        toks_i = jnp.zeros((B,), jnp.int32)
        chains = jnp.zeros((B, k), jnp.int32)
        live = jnp.zeros((B,), bool)
        # sampled builds: the trailing (temp, topk, topp, key) every
        # sampled split/cascade dispatch takes (single mode carries them
        # inside dstate, so its entry needs nothing extra)
        samp_ex = ()
        if self.sampling is not None:
            samp_ex = (
                jnp.ones((B,), jnp.float32), jnp.zeros((B,), jnp.int32),
                jnp.ones((B,), jnp.float32), jnp.zeros((B, 2), jnp.uint32),
            )
        if self.round_mode == "single":
            if self.telemetry:
                return {"round": (self._round_fn, (
                    self.params, self.cache, self.dstate, self._telem_dev,
                    self._c_dev, self._gates,
                ))}
            return {"round": (self._round_fn, (
                self.params, self.cache, self.dstate, self._c_dev, self._gates
            ))}
        verify_args = (self.params, self.cache, toks_i, chains, toks_i, live)
        verify_entry = (
            (self._verify_sampled, verify_args + samp_ex)
            if self.sampling is not None else (self._verify, verify_args)
        )
        if self.mode == "legacy":
            out = {"decode": (self._decode, (
                self.params, self.cache, jnp.zeros((B, 1), jnp.int32),
                self._gates,
            ))}
            out["verify"] = verify_entry
            return out
        if self.mode == "chain_fused":
            out = {}
            if self.draft_spec is not None:
                out["chain_draft"] = (self._draft_fn(k), (
                    self.params, self.cache, toks_i, chains, toks_i,
                    jnp.full((B,), k, jnp.int32), self._gates,
                ))
            out["verify"] = verify_entry
            return out
        # tree_fused / cascade_fused (split): a seeded padded tree
        from repro.core.tree import tree_seed_arrays as _seed

        seed = _seed(np.zeros(B, np.int32), np.zeros((B, k), np.int32),
                     np.zeros(B, np.int32), self.tree_bucket, pld_alpha=0.5)
        tree = tuple(jnp.asarray(a) for a in seed)
        tok, par, dep, pac, msk, cnt = tree
        scal = (jnp.zeros((B,), jnp.int32), jnp.zeros((B,), jnp.float32),
                jnp.asarray(0.5, jnp.float32),
                jnp.asarray(self.t_min, jnp.float32))
        tv_args = (self.params, self.cache, tok, par, dep, msk, cnt, live)
        tv_entry = (
            (self._tree_verify_sampled, tv_args + samp_ex)
            if self.sampling is not None else (self._tree_verify, tv_args)
        )
        if self.mode == "tree_fused":
            out = {}
            if self.draft_spec is not None:
                out["tree_draft"] = (
                    self._tree_draft_fn(self.tree_expansions),
                    (self.params, self.cache) + tree + scal + (self._gates,),
                )
            out["tree_verify"] = tv_entry
            return out
        bank = self.bank
        probe = jnp.full((B,), -1, jnp.int32)
        apply = jnp.zeros((B,), bool)
        alphas = jnp.full((B,), 0.5, jnp.float32)
        out = {"cascade_draft": (
            self._casc_draft_fn(self.tree_expansions),
            (bank.drafter.params, self.cache) + tree + scal
            + (self._level_gates[bank.drafter.index],),
        )}
        if bank.rescorers:
            for lvl in bank.rescorers[:-1]:
                out[f"rescore_l{lvl.index}"] = (self._rescore_fn(lvl.index), (
                    lvl.params, self.cache) + tree
                    + (probe, apply, alphas, self._level_gates[lvl.index])
                    + samp_ex,
                )
            last = bank.rescorers[-1]
            telem_args = (
                (self._telem_dev, toks_i, toks_i) if self.telemetry else ()
            )
            out["rescore_verify"] = (self._rescore_verify_fn(last.index), (
                last.params, self.params, self.cache) + tree
                + (probe, apply, alphas, self._level_gates[last.index], live)
                + telem_args + samp_ex,
            )
        else:
            out["tree_verify"] = tv_entry
        return out

    # ------------------------------------------------------------- stepping
    def _pld_chains(self):
        """Per-slot PLD proposals (B, k) — free host-side retrieval drafts.

        Also records where PLD ends per slot: the acceptance estimator that
        prices the NEURAL draft must only see neural-token outcomes."""
        chains = np.zeros((self.B, self.k), np.int32)
        have = np.zeros(self.B, np.int32)
        for b in range(self.B):
            if not self.live[b]:
                continue
            ctx = np.asarray(self.contexts[b] + [int(self.pending[b])], np.int64)
            toks = self.pld.propose(ctx, self.k)
            chains[b, : len(toks)] = toks
            have[b] = len(toks)
        self._pld_have = have.copy()
        return chains, have

    def _propose(self):
        """Per-slot draft chains (B, k) — PLD first, neural fill-in.

        Returns (chains (B,k) int32, have (B,) int32). The neural fill-in is
        a single fused scan dispatch covering every slot and draft step."""
        with self._span("serve.plan"):
            chains, have = self._pld_chains()
            limit = np.zeros(self.B, np.int32)
            for b in range(self.B):
                if self.live[b]:
                    limit[b] = self._slot_limit(b)
            self._last_limit = limit.copy()   # split-round telemetry
        if self.draft_spec is None:
            return chains, have
        if self.fused:
            return self._propose_fused(chains, have, limit)
        return self._propose_legacy(chains, have, limit)

    def _propose_fused(self, chains, have, limit):
        # one jitted lax.scan over draft steps; trip count = the largest
        # per-slot budget still needing neural fill (<= k distinct compiles)
        steps = int(np.max(np.where(limit > have, limit, 0), initial=0))
        if steps == 0:
            return chains, have
        with self._span("serve.dispatch") as d:
            out = self._draft_fn(steps)(
                self.params, self.cache,
                jnp.asarray(self.pending, jnp.int32),
                jnp.asarray(chains), jnp.asarray(have), jnp.asarray(limit),
                self._gates,
            )
        (ch_d, hv_d), dt = self._wait(out, d)
        with self._span("serve.commit"):
            chains, have = np.asarray(ch_d), np.asarray(hv_d)
            self.stats["draft_dispatches"] += 1
            self.stats["drafted_tokens"] += steps
            # per-draft-step latency (the whole batch advances one token
            # per step) -> c_hat = draft-step / verify-round, the c in T_SD
            self.costs.observe("chain_draft", dt, tokens=steps)
        return chains, have

    def _propose_legacy(self, chains, have, limit):
        # seed behavior: one _decode dispatch per draft step, host syncs
        # between steps (kept only as the A/B baseline for benchmarks)
        need = self.live & (limit > have)
        if not need.any():
            return chains, have
        lo, hi = int(have[need].min()), int(limit[need].max())
        for j in range(lo, hi):
            toks = np.concatenate(
                [self.pending[:, None], chains[:, :j]], axis=1
            ).astype(np.int32)
            with self._span("serve.dispatch") as d:
                logits, _ = self._decode(
                    self.params, self.cache, jnp.asarray(toks), self._gates
                )
                nxt = jnp.argmax(logits[:, -1], -1)
            nxt = np.asarray(self._wait(nxt, d)[0])
            self.stats["draft_dispatches"] += 1
            fill = (have <= j) & (j < limit)
            chains[fill, j] = nxt[fill]
            have = np.maximum(have, np.where(fill, j + 1, have)).astype(np.int32)
        return chains, have

    def _host_round_telemetry(self, n_acc, drafted, pld_have, budget) -> None:
        """Accumulate ONE host-synced round into the numpy telemetry twin
        (``telemetry_schema`` layout). Split/legacy/tree/cascade rounds
        materialize these arrays anyway for their Eq. 4 bookkeeping, so
        mirroring them costs no extra device traffic — the device-carried
        buffer is reserved for the single-dispatch rounds that have no sync
        to piggyback on."""
        th = self._telem_host
        li = self.live.astype(np.int32)
        th["rounds"] += li
        th["accepted"] += np.asarray(n_acc, np.int32) * li
        th["drafted"] += np.asarray(drafted, np.int32) * li
        th["pld_tokens"] += np.asarray(pld_have, np.int32) * li
        th["pld_hit_rounds"] += (
            (np.asarray(pld_have) > 0) & self.live
        ).astype(np.int32)
        K1 = th["budget_hist"].shape[1]
        th["budget_hist"][
            np.arange(self.B), np.clip(np.asarray(budget), 0, K1 - 1)
        ] += li

    # ------------------------------------------------- pipelined single rounds
    def _drain(self) -> None:
        """Block once on every in-flight round's outputs (they are usually
        already resolved — later rounds were dispatched behind them) and
        fold their accepted tokens into the output buffer, in round order."""
        if not self._inflight:
            return
        outs, self._inflight = self._inflight, []
        with self._span("serve.wait"):
            jax.block_until_ready([o["n_acc"] for o in outs])
        self.stats["host_syncs"] += 1
        for o in outs:
            acc, n_acc = np.asarray(o["acc"]), np.asarray(o["n_acc"])
            self.stats["drafted_tokens"] += int(np.asarray(o["drafted"]).sum())
            for b in range(self.B):
                nb = int(n_acc[b])
                if nb:
                    self._out_buf.setdefault(b, []).extend(
                        int(t) for t in acc[b, :nb]
                    )
                    self.stats["tokens"] += nb

    def flush(self) -> Dict[int, List[int]]:
        """Drain every in-flight round and return the buffered tokens per
        slot. The pipelined loop calls this every ``sync_every`` rounds and
        before re-binding a slot (admission/retire); split rounds have
        nothing in flight and this is a cheap no-op."""
        with self._span("serve.drain"):
            self._drain()
            self._drain_telemetry()
        out, self._out_buf = self._out_buf, {}
        return out

    def _drain_telemetry(self) -> None:
        """Fold NEW (since the last drain) telemetry counts into the
        registry. Callers guarantee nothing is in flight (``_drain`` ran),
        so the device buffer belongs to a completed round — reading it is a
        plain D2H copy of resolved arrays, never a new host sync (the
        runtime ``host_syncs`` parity with telemetry off is pinned by
        tests/test_telemetry.py)."""
        totals = TM.merge_totals(self._telem_dev, self._telem_host)
        delta = {k: v - self._telem_seen[k] for k, v in totals.items()}
        self._telem_seen = totals
        TM.fold_telemetry(self.metrics, delta)

    def telemetry_totals(self) -> Dict[str, np.ndarray]:
        """Cumulative drained telemetry (device buffer + host twin), keyed
        by the ``telemetry_schema`` names. Drains in-flight rounds first
        (their tokens stay buffered for the next ``flush``)."""
        self._drain()
        self._drain_telemetry()
        return {k: v.copy() for k, v in self._telem_seen.items()}

    def metrics_summary(self) -> Dict[str, Any]:
        """One JSON-able end-of-run summary sourced from the registry and
        the drained telemetry: tokens/step, dispatch/sync accounting, and
        per-level cascade acceptance — what launch/serve.py prints as its
        machine-readable final line."""
        tot = self.telemetry_totals()
        s = self.stats
        steps = max(s["steps"], 1)
        out: Dict[str, Any] = {
            "mode": self.mode,
            "round_mode": self.round_mode,
            "rounds": s["steps"],
            "tokens": s["tokens"],
            "tokens_per_step": s["tokens"] / steps,
            "round_dispatches": s["round_dispatches"],
            "host_syncs": s["host_syncs"],
            "device_wait_s": s["device_wait"],
            "rounds_per_slot": tot["rounds"].tolist(),
            "accepted_per_slot": tot["accepted"].tolist(),
            "drafted_per_slot": tot["drafted"].tolist(),
            "pld_tokens_per_slot": tot["pld_tokens"].tolist(),
        }
        # accept-rate telemetry (meaningful for greedy AND sampled runs;
        # the sampled CI leg pins that sampling reports them): mean tokens
        # committed per round, and the fraction of PROPOSED (PLD + neural)
        # tokens the verify accepted — the always-emitted pending/bonus
        # token is excluded from the numerator
        out["sampled"] = self.sampling is not None
        rounds_t = float(tot["rounds"].sum())
        acc_t = float(tot["accepted"].sum())
        prop_t = float(tot["drafted"].sum() + tot["pld_tokens"].sum())
        out["accepted_per_round"] = acc_t / rounds_t if rounds_t else None
        out["spec_accept_rate"] = (
            (acc_t - rounds_t) / prop_t if prop_t > 0 else None
        )
        if "casc_obs" in tot:
            obs = tot["casc_obs"].sum(axis=1)
            acc = tot["casc_accept"].sum(axis=1)
            out["cascade_acceptance"] = [
                (float(a) / float(o) if o else None)
                for a, o in zip(acc.tolist(), obs.tolist())
            ]
            out["cascade_routed_rounds"] = (
                tot["casc_routed"].sum(axis=1).tolist()
            )
        return out

    def _step_single(self) -> Dict[int, List[int]]:
        """One fused round: dispatch the single jitted round executable and
        return immediately — accepted tokens are drained from already-
        resolved device futures every ``sync_every`` rounds, so the device
        never waits for the host between rounds."""
        with self._span("serve.dispatch"):
            if self.telemetry:
                # the donated buffer is re-bound in the same statement,
                # like the cache/state (REPRO002) — accumulation happened
                # inside the one round dispatch
                (self.cache, self.dstate, self._telem_dev,
                 out) = self._round_fn(
                    self.params, self.cache, self.dstate, self._telem_dev,
                    self._c_dev, self._gates,
                )
            else:
                self.cache, self.dstate, out = self._round_fn(
                    self.params, self.cache, self.dstate, self._c_dev,
                    self._gates,
                )
        self._inflight.append(out)
        self.stats["steps"] += 1
        self.stats["round_dispatches"] += 1
        self.stats["target_calls"] += 1
        if len(self._inflight) >= self.sync_every:
            return self.flush()
        if self._out_buf:    # drained out-of-band (e.g. by an admission)
            out_b, self._out_buf = self._out_buf, {}
            return out_b
        return {}

    def step(self) -> Dict[int, List[int]]:
        """One speculative round for the whole batch; returns new tokens
        (in pipelined single mode: the tokens drained *so far* — possibly
        from earlier rounds, possibly empty between sync points)."""
        with self._span("serve.round"):
            if self.round_mode == "single":
                return self._step_single()
            if self.mode == "tree_fused":
                return self._step_tree()
            if self.mode == "cascade_fused":
                return self._step_cascade()
            return self._step_chain()

    def _wait(self, out, dispatched: TM.span) -> Tuple[Any, float]:
        """Block on a host-synced dispatch's outputs under ``serve.wait``
        (the ``device_wait`` counter). ``dispatched`` is the
        ``serve.dispatch`` span in which the arguments were built and the
        jitted program called. Returns the resolved outputs and the seconds
        from the call to the result — the interval DyTC's cost trackers
        price."""
        with self._span("serve.wait") as w:
            out = jax.block_until_ready(out)
        self.stats["host_syncs"] += 1
        return out, w.t1 - dispatched.t0

    def _step_chain(self) -> Dict[int, List[int]]:
        """One split chain round (``chain_fused`` split / ``legacy``): host
        PLD + the drafting dispatch(es), then one verify dispatch."""
        chains, have = self._propose()
        if self.sampling is not None:
            ds = self.dstate
            with self._span("serve.dispatch") as d:
                out = self._verify_sampled(
                    self.params, self.cache,
                    jnp.asarray(self.pending, jnp.int32),
                    jnp.asarray(chains), jnp.asarray(have),
                    jnp.asarray(self.live),
                    ds["temp"], ds["topk"], ds["topp"], ds["key"],
                )
            (new_cache, n_chain, new_pending, new_key), dt = self._wait(out, d)
            self.dstate = dict(ds, key=new_key)
        else:
            with self._span("serve.dispatch") as d:
                out = self._verify(
                    self.params, self.cache,
                    jnp.asarray(self.pending, jnp.int32),
                    jnp.asarray(chains), jnp.asarray(have),
                    jnp.asarray(self.live),
                )
            (new_cache, _, n_chain, new_pending), dt = self._wait(out, d)
        self.cache = new_cache
        with self._span("serve.commit"):
            self.stats["target_calls"] += 1
            self.costs.observe_target(dt, tokens=1)   # per-round latency
            n_chain = np.asarray(n_chain)
            new_pending = np.asarray(new_pending)
            out: Dict[int, List[int]] = {}
            for b in range(self.B):
                if not self.live[b]:
                    continue
                acc = [int(self.pending[b])] + [
                    int(t) for t in chains[b, : n_chain[b]]
                ]
                self.contexts[b].extend(acc)
                out[b] = acc
                self.stats["tokens"] += len(acc)
                # Eq. 4 EMA over the NEURAL drafter (the alpha paired with
                # the neural scan's c in T_SD): observe the first neural
                # position's outcome, and only when its PLD prefix was
                # fully accepted — otherwise the neural token was never
                # evaluated (DyTC's parent-accepted rule). PLD outcomes
                # never enter this alpha.
                pld_n = int(self._pld_have[b])
                if have[b] > pld_n and n_chain[b] >= pld_n:
                    self.acceptance.observe(
                        self._slot_key(b), n_chain[b] > pld_n
                    )
            self._host_round_telemetry(
                n_chain + 1, np.maximum(have - self._pld_have, 0),
                self._pld_have, self._last_limit,
            )
            self.pending = np.where(
                self.live, new_pending.astype(np.int64), self.pending
            )
            self.stats["steps"] += 1
            return out

    def _step_tree(self) -> Dict[int, List[int]]:
        """One DyTC round for the whole batch: PLD-seeded on-device tree
        growth (ONE fused scan dispatch), then fused verify + path commit
        (ONE target dispatch). Returns accepted tokens per live slot."""
        with self._span("serve.plan"):
            chains, have = self._pld_chains()
            limits = np.zeros(self.B, np.int32)
            alphas = np.full(self.B, 0.5, np.float32)
            for b in range(self.B):
                if self.live[b]:
                    limits[b] = self._slot_tree_budget(b)
                    alphas[b] = self.acceptance.alpha(self._slot_key(b))
            seed = tree_seed_arrays(
                self.pending.astype(np.int32), chains, have, self.tree_bucket,
                pld_alpha=PLD_SPEC.prior_alpha,
            )
            d_tokens, d_parents, d_depth, d_p_acc, d_mask, d_count = (
                jnp.asarray(a) for a in seed
            )
            tokens, parents, count = seed[0], seed[1], seed[5]
            first_neural = np.full(self.B, -1, np.int32)
            expansions = int(limits.max(initial=0))
        if expansions > 0:
            c = self.costs.c_hat(
                "tree_draft", default=float(self.draft_spec.prior_c)
            )
            with self._span("serve.dispatch") as d:
                out = self._tree_draft_fn(expansions)(
                    self.params, self.cache,
                    d_tokens, d_parents, d_depth, d_p_acc, d_mask, d_count,
                    jnp.asarray(limits), jnp.asarray(alphas),
                    jnp.asarray(max(c, 1e-3), jnp.float32),
                    jnp.asarray(self.t_min, jnp.float32),
                    self._gates,
                )
            out, dt = self._wait(out, d)
            with self._span("serve.commit"):
                # depth/mask stay on device (only the verify reads them);
                # the host bookkeeping needs tokens/parents/count/first
                d_tokens, d_parents, d_depth, _, d_mask, d_count, d_first = out
                tokens, parents, count, first_neural = (
                    np.asarray(a)
                    for a in (d_tokens, d_parents, d_count, d_first)
                )
                self.stats["draft_dispatches"] += 1
                self.stats["drafted_tokens"] += int(
                    np.clip(count - have - 1, 0, None).sum()
                )
                # per-expansion-step latency -> the c in the Eq. 5 budgets
                self.costs.observe("tree_draft", dt, tokens=expansions)

        if self.sampling is not None:
            ds = self.dstate
            with self._span("serve.dispatch") as d:
                out = self._tree_verify_sampled(
                    self.params, self.cache,
                    d_tokens, d_parents, d_depth, d_mask, d_count,
                    jnp.asarray(self.live),
                    ds["temp"], ds["topk"], ds["topp"], ds["key"],
                )
            (new_cache, path, n_acc, bonus, new_key), dt = self._wait(out, d)
            self.dstate = dict(ds, key=new_key)
        else:
            with self._span("serve.dispatch") as d:
                out = self._tree_verify(
                    self.params, self.cache,
                    d_tokens, d_parents, d_depth, d_mask, d_count,
                    jnp.asarray(self.live),
                )
            (new_cache, path, n_acc, bonus), dt = self._wait(out, d)
        self.cache = new_cache
        with self._span("serve.commit"):
            self.stats["target_calls"] += 1
            self.costs.observe_target(dt, tokens=1)
            path, n_acc, bonus = (
                np.asarray(path), np.asarray(n_acc), np.asarray(bonus)
            )
            out_toks: Dict[int, List[int]] = {}
            for b in range(self.B):
                if not self.live[b]:
                    continue
                nodes = path[b, : n_acc[b]]
                acc = [int(tokens[b, i]) for i in nodes]
                self.contexts[b].extend(acc)
                out_toks[b] = acc
                self.stats["tokens"] += len(acc)
                # Eq. 4 EMA: observe the slot's first NEURAL top-1
                # prediction, and only when its parent was accepted (DyTC's
                # parent-accepted rule; the root is always accepted). When
                # the drafter's top-1 duplicated an existing PLD child,
                # first_neural aliases that node — the outcome priced is
                # still the neural prediction's.
                fn = int(first_neural[b])
                if fn >= 0:
                    node_set = {int(i) for i in nodes}
                    if int(parents[b, fn]) in node_set:
                        self.acceptance.observe(
                            self._slot_key(b), fn in node_set
                        )
            self._host_round_telemetry(
                n_acc, np.clip(count - have - 1, 0, None), have, limits,
            )
            self.pending = np.where(
                self.live, bonus.astype(np.int64), self.pending
            )
            self.stats["steps"] += 1
            return out_toks

    # --------------------------------------------------------- cascade round
    def _slot_cascade_plan(self, b: int):
        """Eq. 5 routing + budget split for one slot: returns
        ``(expansions, use_rescore, alpha_eff, rescorer_alphas)``. A slot
        whose trackers say the cascade doesn't pay collapses to single-level
        drafting (no rescores) or to PLD-only (no neural work at all)."""
        bank = self.bank
        L = len(bank)
        alphas = [
            self.acceptance.alpha(bank.slot_key(i, b), default=bank.alpha_prior(i))
            for i in range(L)
        ]
        cs = [
            max(self.costs.c_hat(bank.cost_key(i), default=bank.c_prior(i)), 1e-3)
            for i in range(L - 1)
        ] + [max(self.costs.c_hat("cascade_draft", default=bank.c_prior(L - 1)), 1e-3)]
        alpha_eff = float(np.prod(alphas))
        # warm-up counts whichever keys this slot's rounds actually feed:
        # rescored rounds observe slot_key(0), single-level rounds (the only
        # kind a 1-level hierarchy has) observe direct_key
        warm = (self.acceptance.counts(bank.slot_key(0, b))
                + self.acceptance.counts(bank.direct_key(b)))
        if not self.adaptive or warm < self.min_obs:
            return self.tree_expansions, L > 1, alpha_eff, alphas[: L - 1]
        a_dir = self.acceptance.alpha(
            bank.direct_key(b), default=bank.direct_prior()
        )
        exp, use_rescore = best_cascade_plan(
            alphas, cs, a_dir, self.tree_expansions, self.t_min
        )
        use_rescore = use_rescore and L > 1
        if not use_rescore:
            # single-level rounds are priced (and observed) by the direct
            # tracker — the scan's stop rule must use the same alpha the
            # plan chose the budget with, not the stale compositional prior
            alpha_eff = a_dir
        return exp, use_rescore, alpha_eff, alphas[: L - 1]

    def _step_cascade(self) -> Dict[int, List[int]]:
        """One multi-level cascade round for the whole batch (Alg. 1 + §4.1
        hierarchy, fully batched): PLD-seeded trees, ONE drafting scan by
        the cheapest bank level, ONE intermediate-verify dispatch per
        stronger level (skipped when no slot is routed through it), ONE
        fused target verify + commit. Returns accepted tokens per slot."""
        bank = self.bank
        L = len(bank)
        with self._span("serve.plan"):
            chains, have = self._pld_chains()
            exp_b = np.zeros(self.B, np.int32)
            use_rescore = np.zeros(self.B, bool)
            alpha_eff = np.full(self.B, 0.5, np.float32)
            resc_alphas = np.full((max(L - 1, 1), self.B), 0.5, np.float32)
            for b in range(self.B):
                if not self.live[b]:
                    continue
                exp_b[b], use_rescore[b], alpha_eff[b], r_alphas = (
                    self._slot_cascade_plan(b)
                )
                for i, a in enumerate(r_alphas):
                    resc_alphas[i, b] = a
            seed = tree_seed_arrays(
                self.pending.astype(np.int32), chains, have, self.tree_bucket,
                pld_alpha=bank.pld.prior_alpha,
            )
            d_tokens, d_parents, d_depth, d_p_acc, d_mask, d_count = (
                jnp.asarray(a) for a in seed
            )
            first_neural = jnp.full((self.B,), -1, jnp.int32)
            expansions = int(exp_b.max(initial=0))
            c_draft = self.costs.c_hat(
                "cascade_draft", default=bank.c_prior(L - 1)
            )
        if expansions > 0:
            with self._span("serve.dispatch") as d:
                out = self._casc_draft_fn(expansions)(
                    bank.drafter.params, self.cache,
                    d_tokens, d_parents, d_depth, d_p_acc, d_mask, d_count,
                    jnp.asarray(exp_b), jnp.asarray(alpha_eff),
                    jnp.asarray(max(c_draft, 1e-3), jnp.float32),
                    jnp.asarray(self.t_min, jnp.float32),
                    self._level_gates[bank.drafter.index],
                )
            out, dt = self._wait(out, d)
            (d_tokens, d_parents, d_depth, d_p_acc, d_mask, d_count,
             first_neural) = out
            with self._span("serve.commit"):
                self.stats["draft_dispatches"] += 1
                self.stats["drafted_tokens"] += int(
                    np.clip(np.asarray(d_count) - have - 1, 0, None).sum()
                )
                self.costs.observe("cascade_draft", dt, tokens=expansions)

        # vertical rescores: just-above-drafter first, strongest level last,
        # each ONE jitted dispatch; the probe chain carries each level's
        # first own prediction to the next level's Eq. 4 judgement. The
        # STRONGEST level's dispatch also carries the target verify + commit
        # (cascade_rescore_verify, donated cache) — L dispatches per
        # rescored round, not L + 1.
        probe = first_neural
        level_node = np.full(self.B, -1, np.int32)
        live_d = jnp.asarray(self.live)
        # sampled builds: the slot keys thread sequentially through every
        # rescore dispatch (each splits its own uniforms in-dispatch and
        # returns the advanced keys) — mutable so each hop rebinds samp[3]
        samp = None
        if self.sampling is not None:
            ds = self.dstate
            samp = [ds["temp"], ds["topk"], ds["topp"], ds["key"]]
        if use_rescore.any():
            apply = jnp.asarray(use_rescore & self.live)
            for lvl in bank.rescorers:
                r = lvl.index
                last_level = lvl is bank.rescorers[-1]
                extra = tuple(samp) if samp is not None else ()
                if last_level and self.telemetry:
                    # the donated telemetry buffer rides the final fused
                    # dispatch (re-bound below, before any read); it
                    # absorbs the whole round's per-slot tallies plus this
                    # dispatch's own Eq. 4 verdict
                    with self._span("serve.dispatch") as d:
                        out = self._rescore_verify_fn(r)(
                            lvl.params, self.params, self.cache,
                            d_tokens, d_parents, d_depth, d_p_acc, d_mask,
                            d_count, probe, apply,
                            jnp.asarray(resc_alphas[r]),
                            self._level_gates[r], live_d,
                            self._telem_dev, jnp.asarray(have),
                            jnp.asarray(exp_b), *extra,
                        )
                    out, dt = self._wait(out, d)
                    if samp is not None:
                        (d_tokens, d_parents, d_depth, d_p_acc, d_mask,
                         d_count, lvl_node_d, probe_ok, probe_valid,
                         new_cache, path, n_acc, bonus, samp[3],
                         self._telem_dev) = out
                    else:
                        (d_tokens, d_parents, d_depth, d_p_acc, d_mask,
                         d_count, lvl_node_d, probe_ok, probe_valid,
                         new_cache, path, n_acc, bonus,
                         self._telem_dev) = out
                elif last_level:
                    with self._span("serve.dispatch") as d:
                        out = self._rescore_verify_fn(r)(
                            lvl.params, self.params, self.cache,
                            d_tokens, d_parents, d_depth, d_p_acc, d_mask,
                            d_count, probe, apply,
                            jnp.asarray(resc_alphas[r]),
                            self._level_gates[r], live_d, *extra,
                        )
                    out, dt = self._wait(out, d)
                    if samp is not None:
                        (d_tokens, d_parents, d_depth, d_p_acc, d_mask,
                         d_count, lvl_node_d, probe_ok, probe_valid,
                         new_cache, path, n_acc, bonus, samp[3]) = out
                    else:
                        (d_tokens, d_parents, d_depth, d_p_acc, d_mask,
                         d_count, lvl_node_d, probe_ok, probe_valid,
                         new_cache, path, n_acc, bonus) = out
                else:
                    with self._span("serve.dispatch") as d:
                        out = self._rescore_fn(r)(
                            lvl.params, self.cache,
                            d_tokens, d_parents, d_depth, d_p_acc, d_mask,
                            d_count, probe, apply,
                            jnp.asarray(resc_alphas[r]),
                            self._level_gates[r], *extra,
                        )
                    out, dt = self._wait(out, d)
                    if samp is not None:
                        (d_tokens, d_parents, d_depth, d_p_acc, d_mask,
                         d_count, lvl_node_d, probe_ok, probe_valid,
                         samp[3]) = out
                    else:
                        (d_tokens, d_parents, d_depth, d_p_acc, d_mask,
                         d_count, lvl_node_d, probe_ok, probe_valid) = out
                with self._span("serve.commit"):
                    self.stats["rescore_dispatches"] += 1
                    if last_level:
                        # the fused dispatch contains the target verify; its
                        # wall time prices the TARGET round (the level's own
                        # cost coefficient keeps its prior / last split-mode
                        # estimate — see docs/cascade.md)
                        self.stats["target_calls"] += 1
                        self.costs.observe_target(dt, tokens=1)
                    else:
                        self.costs.observe(bank.cost_key(r), dt, tokens=1)
                    # Eq. 4: this level's verdict on level r+1's first token
                    pv, pk = np.asarray(probe_valid), np.asarray(probe_ok)
                    if not (last_level and self.telemetry):
                        # device carriage covered only the final dispatch's
                        # verdict — intermediate rescorers mirror theirs
                        # into the host twin from the same arrays the
                        # trackers read
                        self._telem_host["casc_obs"][r + 1] += pv.astype(
                            np.int32
                        )
                        self._telem_host["casc_accept"][r + 1] += (
                            pv & pk
                        ).astype(np.int32)
                    for b in range(self.B):
                        if pv[b]:
                            self.acceptance.observe(
                                bank.slot_key(r + 1, b), bool(pk[b])
                            )
                probe = lvl_node_d
            level_node = np.asarray(probe)
            self.cache = new_cache
        else:
            if samp is not None:
                with self._span("serve.dispatch") as d:
                    out = self._tree_verify_sampled(
                        self.params, self.cache,
                        d_tokens, d_parents, d_depth, d_mask, d_count,
                        live_d, *samp,
                    )
                (new_cache, path, n_acc, bonus, samp[3]), dt = self._wait(
                    out, d
                )
            else:
                with self._span("serve.dispatch") as d:
                    out = self._tree_verify(
                        self.params, self.cache,
                        d_tokens, d_parents, d_depth, d_mask, d_count,
                        live_d,
                    )
                (new_cache, path, n_acc, bonus), dt = self._wait(out, d)
            self.cache = new_cache
            self.stats["target_calls"] += 1
            self.costs.observe_target(dt, tokens=1)

        with self._span("serve.commit"):
            tokens_h = np.asarray(d_tokens)
            parents_h = np.asarray(d_parents)
            first_h = np.asarray(first_neural)
            path, n_acc, bonus = np.asarray(path), np.asarray(n_acc), np.asarray(bonus)
            rescored_round = bool(use_rescore.any())
            if not (rescored_round and self.telemetry):
                # no rescore_verify dispatch carried the buffer this round
                # (single-level routing, or telemetry off) — host twin carries
                # the per-slot tallies and routing rows instead
                self._host_round_telemetry(
                    n_acc, np.clip(np.asarray(d_count) - have - 1, 0, None),
                    have, exp_b,
                )
                routed = (use_rescore & self.live).astype(np.int32)
                for lv in bank.rescorers:
                    self._telem_host["casc_routed"][lv.index] += routed
                self._telem_host["casc_routed"][bank.drafter.index] += (
                    (exp_b > 0) & self.live
                ).astype(np.int32)
            out_toks: Dict[int, List[int]] = {}
            for b in range(self.B):
                if not self.live[b]:
                    continue
                nodes = path[b, : n_acc[b]]
                acc = [int(tokens_h[b, i]) for i in nodes]
                self.contexts[b].extend(acc)
                out_toks[b] = acc
                self.stats["tokens"] += len(acc)
                node_set = {int(i) for i in nodes}
                # Eq. 4, target-facing (parent-accepted rule): on cascade
                # rounds the observation point is the STRONGEST level's own
                # node; on single-level rounds it is the drafter's first
                # prediction, priced under the slot's direct tracker
                if use_rescore[b]:
                    fn = int(level_node[b])
                    if fn >= 0 and int(parents_h[b, fn]) in node_set:
                        self.acceptance.observe(
                            bank.slot_key(0, b), fn in node_set
                        )
                        # target-facing verdict: row 0 of the cascade tallies
                        # (the device dispatch cannot see the accepted path's
                        # host-side membership test — always host-mirrored)
                        self._telem_host["casc_obs"][0, b] += 1
                        self._telem_host["casc_accept"][0, b] += int(
                            fn in node_set
                        )
                else:
                    fn = int(first_h[b])
                    if fn >= 0 and int(parents_h[b, fn]) in node_set:
                        self.acceptance.observe(bank.direct_key(b), fn in node_set)
                        if L == 1:
                            # a 1-level bank's direct acceptance IS its
                            # target-facing level alpha — keep the plan's
                            # cascade leg priced too
                            self.acceptance.observe(
                                bank.slot_key(0, b), fn in node_set
                            )
                            self._telem_host["casc_obs"][0, b] += 1
                            self._telem_host["casc_accept"][0, b] += int(
                                fn in node_set
                            )
            if samp is not None:
                # the advanced slot keys (threaded through every dispatch above)
                # re-enter the carried state as device arrays — no host copy
                self.dstate = dict(self.dstate, key=samp[3])
            self.pending = np.where(self.live, bonus.astype(np.int64), self.pending)
            self.stats["steps"] += 1
            return out_toks
