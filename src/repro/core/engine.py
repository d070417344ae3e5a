"""CAS-Spec inference engine: DSIA draft execution + tree verification.

Execution modes for layer-gated drafts:
  - "slice": materialize a reduced-depth param pytree per draft config
    (fewer FLOPs — the honest speed of a layer-sparse draft; requires a
    homogeneous layer stack).
  - "mask": one shared executable, gates passed as a traced vector (zero
    recompiles; the TPU serve_step lowers this form).

Cache discipline: drafts are STAGE-ONLY (never committed); only the full
target model's verification staged KV/states are committed, so the cache is
always exact — the losslessness invariant (see models.model docstring).
"""
from __future__ import annotations

import functools
import time
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config.base import BlockKind, ModelConfig
from repro.core import pld as pld_lib
from repro.core.acceptance import AcceptanceTracker, ema_update
from repro.core.dsia import DraftSpec
from repro.core.latency import (
    CostTracker,
    best_chain_length_batched,
    best_tree_expansions_batched,
)
from repro.core.pld import PromptLookup
from repro.core.tree import DraftTree, bucket_for, tree_seed_device
from repro.core import verify as verify_lib
from repro.models import model as M
from repro.models.shard_utils import constrain, data_axis

import dataclasses


def fake_quant_int8(params: dict) -> dict:
    """Per-output-channel symmetric int8 weight fake-quantization (QSpec sim)."""

    def q(w):
        if not isinstance(w, jax.Array) or w.dtype not in (jnp.float32, jnp.bfloat16):
            return w
        if w.ndim < 2:
            return w
        w32 = w.astype(jnp.float32)
        scale = jnp.max(jnp.abs(w32), axis=tuple(range(w.ndim - 1)), keepdims=True) / 127.0
        scale = jnp.maximum(scale, 1e-8)
        return (jnp.round(w32 / scale).clip(-127, 127) * scale).astype(w.dtype)

    return jax.tree.map(q, params)


DRAFT_KV_MODES = ("recompute", "carry")


def _bounded_loop(body, init, steps: int, j_max):
    """Run ``body`` (a ``lax.scan``-style ``(carry, j) -> (carry, None)``)
    either as a static-trip scan (``j_max is None``) or as a
    ``lax.while_loop`` bounded by the traced ``j_max`` (clipped to
    ``steps``). The while form is what lets the single-dispatch round use
    the SAME per-round trip count the split path computes on host —
    decided on device, no sync. Iterations past the point where every
    slot's fill mask is dead are no-ops, so the two forms are
    token-identical."""
    if j_max is None:
        carry, _ = jax.lax.scan(body, init, jnp.arange(steps, dtype=jnp.int32))
        return carry
    j_hi = jnp.minimum(j_max.astype(jnp.int32), steps)

    def w_cond(c):
        return c[1] < j_hi

    def w_body(c):
        carry, j = c
        carry, _ = body(carry, j)
        return carry, j + 1

    carry, _ = jax.lax.while_loop(w_cond, w_body, (init, jnp.int32(0)))
    return carry


def _check_draft_kv(cfg: ModelConfig, draft_kv: str, who: str) -> None:
    if draft_kv not in DRAFT_KV_MODES:
        raise ValueError(
            f"unknown draft_kv {draft_kv!r}; pick one of {DRAFT_KV_MODES}"
        )
    if draft_kv == "carry" and (
        cfg.num_codebooks
        or any(
            cfg.block_kind(i) is not BlockKind.ATTENTION
            for i in range(cfg.num_layers)
        )
    ):
        raise ValueError(
            f"{who}: draft_kv='carry' requires an attention-only text stack "
            "— SSM per-step states are cumulative (not row-scatterable) and "
            "codebook tokens are not scalar; use draft_kv='recompute'"
        )


def chain_draft_scan(
    cfg: ModelConfig,
    steps: int,                       # static scan trip count (<= k)
    params: dict,
    cache: dict,                      # batched committed cache (scratch copy semantics)
    pending: jax.Array,               # (B,) int32 last verified token per slot
    chains: jax.Array,                # (B, k) int32, PLD-prefilled prefix
    have: jax.Array,                  # (B,) int32 tokens already proposed (PLD)
    limit: jax.Array,                 # (B,) int32 per-slot adaptive draft cap
    gates: Optional[jax.Array],       # (num_layers,) DSIA layer gates or None
    *,
    quantize: Optional[str] = None,   # "int8": W8A8 MLP matmuls (static)
    attn_override: Optional[dict] = None,   # efficient-attention DSIA (static)
    draft_kv: str = "recompute",      # "recompute" | "carry" (static)
    dynamic_steps: bool = False,      # trip count from (have, limit), on device
) -> Tuple[jax.Array, jax.Array]:
    """Fused k-step neural chain drafting: one ``lax.scan`` over draft steps.

    Step ``j`` writes the draft argmax at position ``j`` into chain position
    ``j`` only where ``have <= j < limit``; PLD-prefilled positions are never
    overwritten, and slots past their adaptive ``limit`` stop contributing
    draft tokens. Unfilled tail positions hold stale tokens during the scan
    — the causal mask keeps them invisible to every filled position. The
    committed cache is READ-ONLY either way: no scratch commits, no cache
    copy, one dispatch per proposal round instead of ``k`` host-synchronized
    decode calls, and losslessness is untouched.

    ``draft_kv`` picks how draft steps see each other:

      - ``"recompute"`` — each step re-decodes the fixed (B, k+1) block
        ``[pending, chain]`` under a causal tree mask (the same staged-KV
        block mechanism verification uses). O(k^2) token-forwards per round;
        at the paper's k <= 5 the padded block is MXU-absorbed on TPU, and
        this is the only mode that supports SSM stacks (their per-step
        states are recomputed inside the block, never carried).
      - ``"carry"`` — ONE initial (B, k+1) block decode fills carried
        staged-KV buffers and an argmax table, then each step decodes only
        the single appended token against [committed cache ++ carried
        staged KV], scattering its K/V back into the buffers. O(k)
        token-forwards per round; attention-only stacks.

    ``dynamic_steps=True`` replaces the static trip count with the exact
    per-round need ``max_b(limit_b where limit_b > have_b)`` computed on
    device (a ``lax.while_loop``) — what the split serving path computes on
    host per round, available to the fused single-dispatch round without a
    sync. Token-identical to the static scan (the skipped iterations have
    dead fill masks). Caveat: on CPU XLA a dynamic-trip While runs each
    iteration noticeably slower than the known-trip scan, so the fused
    rounds keep the static trip and skip the WHOLE scan via ``lax.cond``
    when no slot needs neural fill; prefer ``dynamic_steps`` only where
    the saved iterations beat the While overhead (accelerators).

    Returns (chains, have) with ``have = max(have, min(limit, steps))``.
    """
    _check_draft_kv(cfg, draft_kv, "chain_draft_scan")
    B, K = chains.shape
    toks = jnp.concatenate([pending[:, None], chains], axis=1)   # (B, K+1)
    mask = jnp.tril(jnp.ones((K + 1, K + 1), bool))
    j_max = (
        jnp.max(jnp.where(limit > have, limit, 0)) if dynamic_steps else None
    )

    if draft_kv == "recompute":
        def body(toks, j):
            logits, _ = M.decode_step(
                cfg, params, cache, toks, gates=gates, tree_mask=mask,
                quantize=quantize, attn_override=attn_override,
            )
            nxt = jnp.argmax(logits, -1).astype(toks.dtype)      # (B, K+1)
            fill = (have <= j) & (j < limit)
            col = jnp.where(fill, nxt[:, j], toks[:, j + 1])
            return toks.at[:, j + 1].set(col), None

        toks = _bounded_loop(body, toks, steps, j_max)
        have = jnp.maximum(have, jnp.minimum(limit, jnp.int32(steps)))
        return toks[:, 1:], have

    # --- carry: one block decode seeds the buffers, then 1-token steps
    base = cache["pos"]                                          # (B,)
    col_ids = jnp.arange(K + 1, dtype=jnp.int32)
    logits0, staged0 = M.decode_step(
        cfg, params, cache, toks, gates=gates, tree_mask=mask,
        quantize=quantize, attn_override=attn_override,
    )
    nxt_buf = jnp.argmax(logits0, -1).astype(toks.dtype)         # (B, K+1)

    def body_carry(carry, j):
        toks, nxt_buf, staged = carry
        fill = (have <= j) & (j < limit)
        col = jnp.where(fill, nxt_buf[:, j], toks[:, j + 1])
        toks = toks.at[:, j + 1].set(col)
        # decode ONLY the appended token; staged rows 0..j are final for
        # every slot by step j (PLD rows from the seed decode, drafted rows
        # re-staged by their own step), so causal row visibility is exact
        smask = jnp.broadcast_to(
            (col_ids[None, None, :] <= j), (B, 1, K + 1)
        )
        logits1, st1 = M.decode_step(
            cfg, params, cache, toks[:, j + 1][:, None], gates=gates,
            q_pos=(base + j + 1)[:, None],
            staged_kv=staged, staged_pos=base[:, None] + col_ids[None],
            staged_mask=smask, quantize=quantize, attn_override=attn_override,
        )
        nxt_buf = nxt_buf.at[:, j + 1].set(
            jnp.argmax(logits1[:, 0], -1).astype(toks.dtype)
        )
        staged = jax.tree.map(
            lambda buf, st: buf.at[:, :, j + 1].set(st[:, :, 0].astype(buf.dtype)),
            staged, st1,
        )
        return (toks, nxt_buf, staged), None

    toks, _, _ = _bounded_loop(body_carry, (toks, nxt_buf, staged0), steps, j_max)
    have = jnp.maximum(have, jnp.minimum(limit, jnp.int32(steps)))
    return toks[:, 1:], have


def tree_draft_scan(
    cfg: ModelConfig,
    expansions: int,                  # static scan trip count (max per-slot budget)
    top_k: int,                       # static sibling candidates per expansion
    params: dict,
    cache: dict,                      # batched committed cache (read-only here)
    tokens: jax.Array,                # (B, N) int32 seeded node tokens (node 0 = pending)
    parents: jax.Array,               # (B, N) int32, -1 at root/unused
    depth: jax.Array,                 # (B, N) int32
    p_acc: jax.Array,                 # (B, N) f32 accumulated acceptance per node
    mask: jax.Array,                  # (B, N, N) bool ancestor-closure (self-only unused)
    count: jax.Array,                 # (B,) int32 nodes used (root + PLD seed)
    limit: jax.Array,                 # (B,) int32 per-slot expansion budget (Eq. 5)
    alpha: jax.Array,                 # (B,) f32 per-slot neural acceptance estimate
    c: jax.Array,                     # () f32 draft cost coefficient (stop rule)
    t_min: jax.Array,                 # () f32 min-speedup threshold (stop rule)
    gates: Optional[jax.Array],       # (num_layers,) DSIA layer gates or None
    *,
    top_p: float = 0.3,
    quantize: Optional[str] = None,   # "int8": W8A8 MLP matmuls (static)
    attn_override: Optional[dict] = None,   # efficient-attention DSIA (static)
    draft_kv: str = "recompute",      # "recompute" | "carry" (static)
    dynamic_steps: bool = False,      # trip count = max per-slot limit, on device
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array, jax.Array,
           jax.Array]:
    """Fused DyTC tree growth: one ``lax.scan`` over expansion steps (§4.2).

    The batched, on-device analogue of ``DyTCScheduler.build_tree``. Each
    scan step obtains the draft's next-token distribution for the padded
    (B, N) node block (the committed cache stays READ-ONLY), then per slot:

      1. picks the active node with the highest accumulated P_acc with a
         ``jnp.argmax`` over the node axis (Alg. 1 line 5 — no host loop),
      2. applies the stop rule P_acc * (alpha/c) < t_min (Alg. 1; the root
         is exempt, mirroring the host scheduler), deactivating the node,
      3. expands: the draft's ``top_k`` next-token candidates become
         children (TOP-P filtered against the top candidate, Alg. 1
         line 19), with token-level P_acc refinement
         ``alpha * sqrt(p_i / p_top)`` as in the host DyTC path. A
         candidate that duplicates an existing child of the leaf (e.g. the
         PLD-seeded chain node the drafter agrees with) is NOT re-added —
         and when the duplicate is the drafter's top-1, ``first_neural``
         aliases the existing node, so the Eq. 4 estimator observes the
         prediction's true accept/reject outcome instead of a spurious
         rejection (the greedy walk always takes the first matching child).

    Slots past their per-slot ``limit`` (the Eq. 5 budget chosen by the
    server from its acceptance/cost trackers) and slots whose tree bucket
    is full stop growing; their carries pass through unchanged, keeping
    every shape jit-stable at the ``TREE_BUCKETS`` padding. Unused node
    slots hold stale tokens — their self-only mask rows keep them invisible
    to every real node, exactly as host-side ``DraftTree.flatten`` pads.

    ``draft_kv`` picks the drafting cost model:

      - ``"recompute"`` — each step re-decodes the whole padded block under
        the dense ancestor-closure mask (the same mechanism verification
        uses): O(E*N) node-forwards per round. Dispatch-free and
        buffer-free; the MXU absorbs the padded block on TPU at small N.
      - ``"carry"`` — ONE seed-block decode fills carried staged-KV buffers
        plus a per-node top-k candidate table, then each expansion step
        decodes only its <= ``top_k`` appended candidates against
        [committed cache ++ carried staged KV] (ancestors via the carried
        buffers, self via the new block, siblings mutually invisible):
        O(N + E*top_k) node-forwards per round — the mode that makes tree
        buckets past N=32 pay. A node's logits depend only on its ancestor
        closure, which never changes after creation, so the cached
        candidates equal what recompute re-derives each step and the two
        modes are token-identical (tests/test_draft_kv_carry.py).

    Returns (tokens, parents, depth, p_acc, mask, count, first_neural)
    where ``first_neural[b]`` is the node index carrying the slot's first
    neural top-1 prediction (-1 if none) — the Eq. 4 observation point.
    """
    _check_draft_kv(cfg, draft_kv, "tree_draft_scan")
    B, N = tokens.shape
    b_idx = jnp.arange(B)
    slot_j = jnp.arange(N)
    active = slot_j[None, :] < count[:, None]          # every seeded node
    first_neural = jnp.full((B,), -1, jnp.int32)
    # dynamic trip count: expansion steps past every slot's limit are
    # complete no-ops (dead select + dropped writes), so stopping at the
    # max per-slot budget is token-identical — the on-device analogue of
    # the split path's host-computed `expansions = limits.max()`
    e_max = jnp.max(limit) if dynamic_steps else None
    alpha = alpha.astype(jnp.float32)
    rate = alpha / jnp.maximum(c.astype(jnp.float32), 1e-6)
    # invariant across expansion steps — read ONCE outside the scan body
    # (drafting never writes the committed cache, so ``pos`` cannot move;
    # tests assert it is untouched after a drafting round)
    base = cache["pos"][:, None]                       # (B, 1)

    def _select(p_acc, active, e):
        """Alg. 1 line 5 + stop rule; returns (leaf, leaf_p, grow, active)."""
        score = jnp.where(active, p_acc, -jnp.inf)
        leaf = jnp.argmax(score, axis=1).astype(jnp.int32)           # (B,)
        valid = jnp.any(active, axis=1) & (e < limit)
        leaf_p = jnp.take_along_axis(p_acc, leaf[:, None], 1)[:, 0]
        # stop rule: least-future-speedup below threshold (root exempt)
        grow = valid & ((leaf == 0) | (leaf_p * rate >= t_min))
        # the selected node is consumed either way (expanded or stopped)
        active = active.at[b_idx, jnp.where(valid, leaf, N)].set(
            False, mode="drop"
        )
        return leaf, leaf_p, grow, active

    def _append(state, grow, leaf, leaf_p, top_vals, top_idx):
        """Expansion bookkeeping — shared VERBATIM by both draft_kv modes,
        which is what makes carry-mode parity with recompute exact: only
        the source of (top_vals, top_idx) differs between them."""
        tokens, parents, depth, p_acc, mask, count, active, first_neural = state
        parent_row = jnp.take_along_axis(mask, leaf[:, None, None], axis=1)[:, 0]
        parent_depth = jnp.take_along_axis(depth, leaf[:, None], 1)[:, 0]
        idxs = []
        for r in range(top_k):   # kept candidates land contiguously at count
            tok_r = top_idx[:, r].astype(jnp.int32)
            # dedup: an existing same-token child of this leaf (PLD seed or
            # earlier expansion) already covers this candidate in the walk
            real_now = slot_j[None, :] < count[:, None]
            dup_cand = (parents == leaf[:, None]) & (tokens == tok_r[:, None]) & real_now
            dup = dup_cand.any(axis=1)
            dup_idx = jnp.argmax(dup_cand, axis=1).astype(jnp.int32)
            keep = grow & ~dup & (count < N)
            if r > 0:   # TOP-P sibling filter (Alg. 1 line 19)
                keep &= top_vals[:, r] >= top_p * top_vals[:, 0]
            idx = jnp.where(keep, count, N)            # N = dropped write
            a_node = jnp.minimum(
                1.0,
                alpha
                * jnp.sqrt(top_vals[:, r] / jnp.maximum(top_vals[:, 0], 1e-9)),
            )
            # a duplicated child was seeded with the PLD prior — the neural
            # drafter just confirmed it, so refresh its P_acc to the neural
            # score (else best-leaf selection undervalues the agreed chain)
            ridx = jnp.where(grow & dup, dup_idx, N)
            old_p = jnp.take_along_axis(
                p_acc, jnp.minimum(ridx, N - 1)[:, None], 1
            )[:, 0]
            p_acc = p_acc.at[b_idx, ridx].set(
                jnp.maximum(old_p, leaf_p * a_node), mode="drop"
            )
            tokens = tokens.at[b_idx, idx].set(tok_r, mode="drop")
            parents = parents.at[b_idx, idx].set(leaf, mode="drop")
            depth = depth.at[b_idx, idx].set(parent_depth + 1, mode="drop")
            p_acc = p_acc.at[b_idx, idx].set(leaf_p * a_node, mode="drop")
            row = parent_row | (slot_j[None, :] == idx[:, None])
            mask = mask.at[b_idx, idx].set(row, mode="drop")
            active = active.at[b_idx, idx].set(True, mode="drop")
            if r == 0:
                # the node carrying the drafter's top-1 outcome: the new
                # child, or the existing duplicate it agrees with
                outcome = jnp.where(grow & dup, dup_idx, jnp.where(keep, idx, N))
                first_neural = jnp.where(
                    (first_neural < 0) & (outcome < N), outcome, first_neural
                )
            count = count + keep.astype(jnp.int32)
            idxs.append(idx)
        state = (tokens, parents, depth, p_acc, mask, count, active, first_neural)
        return state, idxs, parent_row, parent_depth

    if draft_kv == "recompute":
        def body(carry, e):
            tokens, parents, depth, p_acc, mask, count, active, first_neural = carry
            qpos = base + depth
            logits, _ = M.decode_step(
                cfg, params, cache, tokens, gates=gates, tree_mask=mask, q_pos=qpos,
                quantize=quantize, attn_override=attn_override,
            )
            leaf, leaf_p, grow, active = _select(p_acc, active, e)
            lg = jnp.take_along_axis(logits, leaf[:, None, None], axis=1)[:, 0]
            probs = jax.nn.softmax(lg.astype(jnp.float32), axis=-1)  # (B, V)
            top_vals, top_idx = jax.lax.top_k(probs, top_k)
            state = (tokens, parents, depth, p_acc, mask, count, active, first_neural)
            state, _, _, _ = _append(state, grow, leaf, leaf_p, top_vals, top_idx)
            return state, None

        carry = (tokens, parents, depth, p_acc.astype(jnp.float32), mask, count,
                 active, first_neural)
        carry = _bounded_loop(body, carry, expansions, e_max)
        tokens, parents, depth, p_acc, mask, count, _, first_neural = carry
        return tokens, parents, depth, p_acc, mask, count, first_neural

    # --- carry: seed decode fills the buffers + per-node candidate table
    logits0, staged0 = M.decode_step(
        cfg, params, cache, tokens, gates=gates, tree_mask=mask,
        q_pos=base + depth, quantize=quantize, attn_override=attn_override,
    )
    probs0 = jax.nn.softmax(logits0.astype(jnp.float32), axis=-1)    # (B, N, V)
    cand_v, cand_i = jax.lax.top_k(probs0, top_k)                    # (B, N, k)
    cand_i = cand_i.astype(jnp.int32)

    def body_carry(carry, e):
        (tokens, parents, depth, p_acc, mask, count, active, first_neural,
         staged, cand_v, cand_i) = carry
        leaf, leaf_p, grow, active = _select(p_acc, active, e)
        top_vals = jnp.take_along_axis(cand_v, leaf[:, None, None], axis=1)[:, 0]
        top_idx = jnp.take_along_axis(cand_i, leaf[:, None, None], axis=1)[:, 0]
        state = (tokens, parents, depth, p_acc, mask, count, active, first_neural)
        state, idxs, parent_row, parent_depth = _append(
            state, grow, leaf, leaf_p, top_vals, top_idx
        )
        tokens, parents, depth, p_acc, mask, count, active, first_neural = state
        # decode ONLY the <= top_k appended candidates against [committed
        # cache ++ carried staged KV]: ancestors come from the buffers via
        # the leaf's closure row, self-visibility from the new block, and
        # siblings stay mutually invisible (eye mask) — exactly the rows
        # the recompute block decode exposes to these nodes. Dropped
        # (duplicate) candidates decode too (jit-stable block); their
        # buffer writes land on index N and are dropped.
        qpos_new = jnp.broadcast_to(
            (base[:, 0] + parent_depth + 1)[:, None], (B, top_k)
        )
        svis = jnp.broadcast_to(parent_row[:, None, :], (B, top_k, N))
        logits_n, st_n = M.decode_step(
            cfg, params, cache, top_idx.astype(jnp.int32), gates=gates,
            tree_mask=jnp.eye(top_k, dtype=bool), q_pos=qpos_new,
            staged_kv=staged, staged_pos=base + depth, staged_mask=svis,
            quantize=quantize, attn_override=attn_override,
        )
        probs_n = jax.nn.softmax(logits_n.astype(jnp.float32), axis=-1)
        cv_n, ci_n = jax.lax.top_k(probs_n, top_k)       # (B, top_k, top_k)
        idxs_arr = jnp.stack(idxs, axis=1)               # (B, top_k)
        staged = jax.tree.map(
            lambda buf, st: buf.at[:, b_idx[:, None], idxs_arr].set(
                st.astype(buf.dtype), mode="drop"
            ),
            staged, st_n,
        )
        cand_v = cand_v.at[b_idx[:, None], idxs_arr].set(cv_n, mode="drop")
        cand_i = cand_i.at[b_idx[:, None], idxs_arr].set(
            ci_n.astype(jnp.int32), mode="drop"
        )
        return (tokens, parents, depth, p_acc, mask, count, active,
                first_neural, staged, cand_v, cand_i), None

    carry = (tokens, parents, depth, p_acc.astype(jnp.float32), mask, count,
             active, first_neural, staged0, cand_v, cand_i)
    carry = _bounded_loop(body_carry, carry, expansions, e_max)
    tokens, parents, depth, p_acc, mask, count, _, first_neural = carry[:8]
    return tokens, parents, depth, p_acc, mask, count, first_neural


def cascade_rescore(
    cfg: ModelConfig,
    params: dict,
    cache: dict,                      # batched committed cache (read-only here)
    tokens: jax.Array,                # (B, N) int32 node tokens from the level below
    parents: jax.Array,               # (B, N) int32 (-1 root, -2 pruned, N unused)
    depth: jax.Array,                 # (B, N) int32
    p_acc: jax.Array,                 # (B, N) f32
    mask: jax.Array,                  # (B, N, N) bool ancestor closure
    count: jax.Array,                 # (B,) int32 node slots consumed
    probe: jax.Array,                 # (B,) int32 node whose verdict to report (-1 none)
    apply: jax.Array,                 # (B,) bool: slots routed through this level
    alpha: jax.Array,                 # (B,) f32 this level's acceptance estimate
    gates: Optional[jax.Array],       # (num_layers,) this level's DSIA gates
    *,
    quantize: Optional[str] = None,   # "int8": W8A8 MLP matmuls (static)
    attn_override: Optional[dict] = None,   # efficient-attention DSIA (static)
    attn_backend: Optional[str] = None,     # "pallas": kernel intra-tree pass
    sampling: Optional[tuple] = None, # (temp (B,), top_k (B,), top_p (B,),
                                      #  u (B, N+2)) -> stochastic rescore
):
    """ONE intermediate-verify dispatch of a stronger cascade level — the
    batched, on-device form of Alg. 1's level-to-level acceptance (the
    vertical-cascade "verify and extend" that ``VCScheduler`` runs host-side
    one request at a time, recast tree-natively).

    The level decodes the whole padded node block under the ancestor-closure
    masks (committed cache READ-ONLY — exactly the verification mechanism)
    and then, per slot where ``apply``:

      1. **endorse** — a node whose token equals this level's argmax at its
         parent, with every proper ancestor likewise endorsed, is confirmed:
         its P_acc is refreshed to this level's (stronger) estimate;
      2. **hedge** — at the SHALLOWEST first-mismatch node, the level adds
         its own argmax continuation as a *sibling* (skipped when an
         endorsed sibling already carries that token). The cheaper level's
         node is KEPT: the target may still accept it, and a tree hedges
         instead of overwriting — this makes the rescored tree a strict
         superset of the drafted tree, so a cascade round can never accept
         fewer tokens than the drafter alone (the tree-cascade analogue of
         "verify"; a chain cascade would truncate here);
      3. **extend** — the deepest fully-endorsed node gets one new child
         carrying this level's argmax continuation (the analogue of
         "extend"; skipped when a sibling already carries that token, e.g.
         the hedge node, or when the bucket is full).

    Slots with ``apply=False`` pass through untouched (they ride the same
    dispatch — per-slot routing never changes the executable).

    Returns ``(tokens, parents, depth, p_acc, mask, count, level_node,
    probe_ok, probe_valid)``: ``level_node[b]`` is the depth-1 node carrying
    this level's own continuation of the root (-1 if none) — the next
    level's Eq. 4 observation point, always judgeable because the root is
    the target's own pending token; ``probe_ok``/``probe_valid`` report this
    level's verdict on the INPUT node ``probe[b]`` (the level below's first
    own prediction), valid only when the probe's ancestors were all
    endorsed (DyTC's parent-accepted rule).

    ``sampling`` switches on the level-to-level STOCHASTIC rescore rule:
    a node is endorsed with prob q_level[parent](token) — one carried
    uniform per node against this level's warped distribution — and the
    hedge/extend continuations become inverse-CDF draws from q_level
    instead of argmaxes (the last two uniforms). This is proposal shaping
    only: losslessness is owned entirely by the FINAL target verify (the
    stochastic tree walk in ``cascade_rescore_verify``), which is
    distribution-preserving for ANY proposal tree.
    """
    B, N = tokens.shape
    b_idx = jnp.arange(B)
    slot_j = jnp.arange(N)
    qpos = cache["pos"][:, None] + depth
    logits, _ = M.decode_step(
        cfg, params, cache, tokens, gates=gates, tree_mask=mask, q_pos=qpos,
        quantize=quantize, attn_override=attn_override,
        attn_backend=attn_backend,
    )
    nxt = jnp.argmax(logits, -1).astype(jnp.int32)               # (B, N)

    real = slot_j[None, :] < count[:, None]
    has_parent = real & (parents >= 0)                           # non-root live
    p_clip = jnp.clip(parents, 0, N - 1)
    parent_nxt = jnp.take_along_axis(nxt, p_clip, axis=1)        # (B, N)
    if sampling is None:
        ok = jnp.where(has_parent, tokens == parent_nxt, True)
    else:
        s_temp, s_topk, s_topp, s_u = sampling
        q_lvl = verify_lib.sampling_probs(logits, s_temp, s_topk, s_topp)
        q_par = jnp.take_along_axis(q_lvl, p_clip[:, :, None], axis=1)
        tok_p = jnp.take_along_axis(q_par, tokens[..., None], -1)[..., 0]
        ok = jnp.where(has_parent, s_u[:, :N] < tok_p, True)
    bad = has_parent & ~ok
    eye = jnp.eye(N, dtype=bool)[None]
    anc_bad = (mask & ~eye & bad[:, None, :]).any(-1)            # bad proper ancestor
    # probe verdict BEFORE any mutation (the level below's first prediction)
    probe_c = jnp.clip(probe, 0, N - 1)
    probe_valid = apply & (probe >= 0) & ~jnp.take_along_axis(
        anc_bad, probe_c[:, None], 1
    )[:, 0]
    probe_ok = jnp.take_along_axis(ok, probe_c[:, None], 1)[:, 0] & probe_valid

    alpha = alpha.astype(jnp.float32)
    parent_p = jnp.take_along_axis(p_acc, p_clip, axis=1)
    endorsed = real & ~bad & ~anc_bad                            # root included
    # endorse: refresh P_acc to this (stronger) level's estimate, exactly
    # like the drafting scan refreshes a confirmed PLD seed
    p_acc = jnp.where(
        endorsed & has_parent & apply[:, None],
        jnp.maximum(p_acc, parent_p * alpha[:, None]), p_acc,
    )

    def _append(tokens, parents, depth, p_acc, mask, count, at, tok, want):
        """Add one child per slot under node ``at`` carrying ``tok`` (drop
        when a sibling already has that token, the bucket is full, or
        ``want`` is off). Returns updated arrays + the kept mask."""
        real_now = slot_j[None, :] < count[:, None]
        sib = (parents == at[:, None]) & real_now & (tokens == tok[:, None])
        keep = want & ~sib.any(axis=1) & (count < N)
        idx = jnp.where(keep, count, N)                          # N = dropped
        a_depth = jnp.take_along_axis(depth, at[:, None], 1)[:, 0]
        a_p = jnp.take_along_axis(p_acc, at[:, None], 1)[:, 0]
        a_row = jnp.take_along_axis(mask, at[:, None, None], axis=1)[:, 0]
        tokens = tokens.at[b_idx, idx].set(tok, mode="drop")
        parents = parents.at[b_idx, idx].set(at, mode="drop")
        depth = depth.at[b_idx, idx].set(a_depth + 1, mode="drop")
        p_acc = p_acc.at[b_idx, idx].set(a_p * alpha, mode="drop")
        mask = mask.at[b_idx, idx].set(
            a_row | (slot_j[None, :] == idx[:, None]), mode="drop"
        )
        count = count + keep.astype(jnp.int32)
        return tokens, parents, depth, p_acc, mask, count, keep

    state = (tokens, parents, depth, p_acc, mask, count)
    # hedge: a sibling with this level's own continuation at the SHALLOWEST
    # first-mismatch (the most probable rejection point of the drafted tree)
    cand = bad & ~anc_bad
    has_hedge = cand.any(axis=1)
    hedge_src = jnp.argmin(jnp.where(cand, depth, N + 1), axis=1).astype(jnp.int32)
    hedge_at = jnp.take_along_axis(p_clip, hedge_src[:, None], 1)[:, 0]
    if sampling is None:
        hedge_tok = jnp.take_along_axis(parent_nxt, hedge_src[:, None], 1)[:, 0]
    else:
        q_h = jnp.take_along_axis(q_lvl, hedge_at[:, None, None], axis=1)[:, 0]
        hedge_tok = verify_lib._inv_cdf(q_h, s_u[:, N])
    state = _append(*state, jnp.where(has_hedge, hedge_at, 0),
                    hedge_tok, apply & has_hedge)[:-1]
    # extend: one child below the deepest fully-endorsed node
    frontier = jnp.argmax(jnp.where(endorsed, depth, -1), axis=1).astype(jnp.int32)
    if sampling is None:
        ext_tok = jnp.take_along_axis(nxt, frontier[:, None], 1)[:, 0]
    else:
        q_f = jnp.take_along_axis(q_lvl, frontier[:, None, None], axis=1)[:, 0]
        ext_tok = verify_lib._inv_cdf(q_f, s_u[:, N + 1])
    state = _append(*state, frontier, ext_tok, apply)[:-1]
    tokens, parents, depth, p_acc, mask, count = state

    # this level's Eq. 4 observation point: the depth-1 node carrying its
    # argmax continuation of the ROOT (the root is the target's own pending
    # token, so the node's parent is ALWAYS accepted — first-token
    # acceptance, exactly the chain path's estimator). After substitution /
    # extension such a node exists whenever the slot was rescored: an
    # endorsed draft child, a substituted child, or the appended extension
    # when the tree was empty. An endorsed child counts as this level's own
    # prediction — endorsement means its token EQUALS this level's argmax.
    root_nxt = nxt[:, 0]
    real_now = slot_j[None, :] < count[:, None]                  # incl. appended
    lvl_cand = (parents == 0) & real_now & (tokens == root_nxt[:, None])
    level_node = jnp.where(
        apply & lvl_cand.any(axis=1),
        jnp.argmax(lvl_cand, axis=1).astype(jnp.int32),
        jnp.int32(-1),
    )
    return (tokens, parents, depth, p_acc, mask, count,
            level_node, probe_ok, probe_valid)


def verify_accept_commit(
    cfg: ModelConfig,
    params: dict,
    cache: dict,
    pending: jax.Array,               # (B,) int32
    chains: jax.Array,                # (B, k) int32
    have: jax.Array,                  # (B,) int32
    live: jax.Array,                  # (B,) bool
):
    """One fused target round for chain proposals: verify [pending, chain]
    jointly, accept the longest matching prefix per slot (vectorized — no
    per-slot Python), and commit the accepted path.
    Returns (cache, nxt, n_chain, new_pending)."""
    toks = jnp.concatenate([pending[:, None], chains], axis=1)   # (B, k+1)
    logits, staged = M.decode_step(cfg, params, cache, toks)
    nxt = jnp.argmax(logits, -1).astype(jnp.int32)               # (B, k+1)
    B, K = chains.shape
    ok = (chains == nxt[:, :K]) & (jnp.arange(K)[None] < have[:, None])
    # accepted chain prefix length: leading run of matches
    n_chain = jnp.cumprod(ok.astype(jnp.int32), axis=1).sum(axis=1)
    n_chain = jnp.where(live, n_chain, 0)
    n_acc = jnp.where(live, n_chain + 1, 0).astype(jnp.int32)    # + pending
    new_pending = jnp.take_along_axis(nxt, n_chain[:, None], axis=1)[:, 0]
    path_idx = jnp.broadcast_to(
        jnp.arange(K + 1, dtype=jnp.int32)[None], (B, K + 1)
    )
    new_cache = M.commit_cache(cfg, cache, staged, path_idx, n_acc)
    return new_cache, nxt, n_chain, new_pending


def verify_accept_commit_sampled(
    cfg: ModelConfig,
    params: dict,
    cache: dict,
    pending: jax.Array,               # (B,) int32
    chains: jax.Array,                # (B, k) int32
    have: jax.Array,                  # (B,) int32
    live: jax.Array,                  # (B,) bool
    temp: jax.Array,                  # (B,) f32, <= 0 -> greedy point mass
    top_k: jax.Array,                 # (B,) int32
    top_p: jax.Array,                 # (B,) f32
    u: jax.Array,                     # (B, k+1) f32 round uniforms
):
    """Sampled twin of ``verify_accept_commit``: the same fused target
    round, but acceptance is Leviathan speculative sampling against the
    warped target distribution (point-mass drafts — see
    ``verify.sample_accept_chain_batched``) instead of argmax matching.
    Slots with ``temp <= 0`` get a one-hot q, which reproduces the greedy
    accept/bonus rule token-for-token. The uniforms arrive pre-split from
    the carried per-slot PRNG key — no key ever leaves the device.
    Returns (cache, n_chain, new_pending)."""
    toks = jnp.concatenate([pending[:, None], chains], axis=1)   # (B, k+1)
    logits, staged = M.decode_step(cfg, params, cache, toks)
    B, K = chains.shape
    q = verify_lib.sampling_probs(logits, temp, top_k, top_p)    # (B, k+1, V)
    n_chain, nxt_tok = verify_lib.sample_accept_chain_batched(
        chains, have, q, u[:, :K], u[:, K]
    )
    n_chain = jnp.where(live, n_chain, 0)
    n_acc = jnp.where(live, n_chain + 1, 0).astype(jnp.int32)    # + pending
    path_idx = jnp.broadcast_to(
        jnp.arange(K + 1, dtype=jnp.int32)[None], (B, K + 1)
    )
    new_cache = M.commit_cache(cfg, cache, staged, path_idx, n_acc)
    return new_cache, n_chain, nxt_tok


def tree_verify_accept_commit(
    cfg: ModelConfig,
    params: dict,
    cache: dict,
    tokens: jax.Array,                # (B, N) int32 padded tree node tokens
    parents: jax.Array,               # (B, N) int32, -1 at root/unused
    depth: jax.Array,                 # (B, N) int32
    mask: jax.Array,                  # (B, N, N) bool ancestor closure
    count: jax.Array,                 # (B,) int32 real nodes per slot
    live: jax.Array,                  # (B,) bool
    *,
    attn_backend: Optional[str] = None,
):
    """One fused target round for tree proposals: decode the whole padded
    node block jointly under per-slot ancestor-closure masks (the intra-tree
    attention half routes through ``kernels.tree_attention`` when
    ``attn_backend="pallas"``), walk the longest target-greedy path per slot
    with a vectorized tree walk, and commit the accepted path's staged KV.
    Returns (cache, path_idx (B,N), n_acc (B,), bonus (B,))."""
    qpos = cache["pos"][:, None] + depth
    logits, staged = M.decode_step(
        cfg, params, cache, tokens, tree_mask=mask, q_pos=qpos,
        attn_backend=attn_backend,
    )
    nxt = jnp.argmax(logits, -1).astype(jnp.int32)               # (B, N)
    path, n_acc, bonus = verify_lib.greedy_accept_tree_batched(
        tokens, parents, count, nxt
    )
    n_acc = jnp.where(live, n_acc, 0).astype(jnp.int32)
    new_cache = M.commit_cache(cfg, cache, staged, path, n_acc)
    return new_cache, path, n_acc, bonus


def tree_verify_accept_commit_sampled(
    cfg: ModelConfig,
    params: dict,
    cache: dict,
    tokens: jax.Array,                # (B, N) int32 padded tree node tokens
    parents: jax.Array,               # (B, N) int32, -1 at root/unused
    depth: jax.Array,                 # (B, N) int32
    mask: jax.Array,                  # (B, N, N) bool ancestor closure
    count: jax.Array,                 # (B,) int32 real nodes per slot
    live: jax.Array,                  # (B,) bool
    temp: jax.Array,                  # (B,) f32, <= 0 -> greedy point mass
    top_k: jax.Array,                 # (B,) int32
    top_p: jax.Array,                 # (B,) f32
    u: jax.Array,                     # (B, N) f32 one uniform per walk step
    *,
    attn_backend: Optional[str] = None,
):
    """Sampled twin of ``tree_verify_accept_commit``: the same fused target
    decode + commit, but the accepted path comes from the stochastic tree
    walk (``verify.sample_accept_tree_batched`` — the tree-native
    speculative-sampling rule for point-mass drafts, distribution-
    preserving at every step). temp <= 0 slots reproduce the greedy walk
    token-for-token. Returns (cache, path, n_acc, next_tok)."""
    qpos = cache["pos"][:, None] + depth
    logits, staged = M.decode_step(
        cfg, params, cache, tokens, tree_mask=mask, q_pos=qpos,
        attn_backend=attn_backend,
    )
    q = verify_lib.sampling_probs(logits, temp, top_k, top_p)    # (B, N, V)
    path, n_acc, nxt_tok = verify_lib.sample_accept_tree_batched(
        tokens, parents, count, q, u
    )
    n_acc = jnp.where(live, n_acc, 0).astype(jnp.int32)
    new_cache = M.commit_cache(cfg, cache, staged, path, n_acc)
    return new_cache, path, n_acc, nxt_tok


def cascade_rescore_verify(
    cfg: ModelConfig,
    level_params: dict,
    target_params: dict,
    cache: dict,
    tokens: jax.Array,
    parents: jax.Array,
    depth: jax.Array,
    p_acc: jax.Array,
    mask: jax.Array,
    count: jax.Array,
    probe: jax.Array,
    apply: jax.Array,
    alpha: jax.Array,
    gates: Optional[jax.Array],
    live: jax.Array,
    *,
    quantize: Optional[str] = None,
    attn_override: Optional[dict] = None,
    attn_backend: Optional[str] = None,
    sampling: Optional[tuple] = None,  # (temp, top_k, top_p, key (B,2) u32)
):
    """The cascade's LAST rescore dispatch with the target verify folded in:
    one jitted call runs the strongest level's ``cascade_rescore`` and then
    the target's ``tree_verify_accept_commit`` over the rescored tree, so an
    L-level cascade round is 1 draft + (L-2) rescores + 1 rescore-and-verify
    dispatch — and the commit scatter can alias a donated cache in place.
    On a mesh the per-slot tree arrays are pinned to their data-parallel
    placement on entry and exit (``_pin_batch``; no-op off-mesh), so the
    fused dispatch neither regathers the proposal nor reshards the cache it
    commits into.

    ``sampling`` carries the per-slot warp params and the slot PRNG keys:
    the keys are split IN-dispatch into the stochastic-rescore uniforms
    (N+2) plus the stochastic tree-walk uniforms (N), the rescore runs the
    level-to-level stochastic rule, and the final verify becomes the
    distribution-preserving stochastic walk against the warped TARGET
    distribution — same dispatch count, zero host syncs, and an extra
    trailing output: the advanced keys.

    Returns the rescore outputs followed by (cache, path, n_acc, bonus)
    [+ new_key when sampled]."""
    dax = data_axis()
    (tokens, parents, depth, p_acc, mask, count, probe, apply, alpha,
     live) = _pin_batch(
        (tokens, parents, depth, p_acc, mask, count, probe, apply, alpha,
         live), dax,
    )
    N = tokens.shape[1]
    resc_sampling = None
    if sampling is not None:
        s_temp, s_topk, s_topp, key = sampling
        new_key, u = verify_lib.round_uniforms(key, 2 * N + 2)
        resc_sampling = (s_temp, s_topk, s_topp, u[:, :N + 2])
    (tokens, parents, depth, p_acc, mask, count, level_node, probe_ok,
     probe_valid) = cascade_rescore(
        cfg, level_params, cache, tokens, parents, depth, p_acc, mask, count,
        probe, apply, alpha, gates,
        quantize=quantize, attn_override=attn_override,
        attn_backend=attn_backend, sampling=resc_sampling,
    )
    if sampling is None:
        new_cache, path, n_acc, bonus = tree_verify_accept_commit(
            cfg, target_params, cache, tokens, parents, depth, mask, count,
            live, attn_backend=attn_backend,
        )
    else:
        new_cache, path, n_acc, bonus = tree_verify_accept_commit_sampled(
            cfg, target_params, cache, tokens, parents, depth, mask, count,
            live, s_temp, s_topk, s_topp, u[:, N + 2:],
            attn_backend=attn_backend,
        )
    (tokens, parents, depth, p_acc, mask, count, level_node, probe_ok,
     probe_valid, path, n_acc, bonus) = _pin_batch(
        (tokens, parents, depth, p_acc, mask, count, level_node, probe_ok,
         probe_valid, path, n_acc, bonus), dax,
    )
    out = (tokens, parents, depth, p_acc, mask, count, level_node, probe_ok,
           probe_valid, new_cache, path, n_acc, bonus)
    return out if sampling is None else out + (new_key,)


# ===================================================== single-dispatch rounds
def _pin_batch(tree, dax):
    """Pin every array in ``tree`` (a dict or flat sequence of per-slot
    arrays, leading dim = batch) to the data-parallel axes. On a mesh this
    keeps the carried round state resident in its data-sharded placement —
    the round's outputs then alias the donated inputs with NO resharding
    collective between rounds; off-mesh ``constrain`` no-ops, so
    single-device rounds lower to byte-identical executables."""
    if dax is None:
        return tree
    if isinstance(tree, dict):
        return {
            k: constrain(v, dax, *([None] * (v.ndim - 1)))
            for k, v in tree.items()
        }
    return type(tree)(
        constrain(v, dax, *([None] * (v.ndim - 1))) for v in tree
    )


def _round_prologue(cfg, cache, state, draft_k, max_ngram, min_ngram):
    """Shared head of the fused rounds: append the pending token to the
    device context buffer and retrieve PLD proposals for every slot inside
    the round executable. Returns (ctx, chains, have) with dead slots'
    proposals zeroed."""
    B, L = state["ctx"].shape
    b_idx = jnp.arange(B)
    n = cache["pos"]
    live = state["live"]
    # writing pending at position n IS the commit of this round's first
    # accepted token (the pending token is always accepted when live), so
    # the buffer stays consistent whatever the round accepts
    ctx = state["ctx"].at[b_idx, jnp.where(n < L, n, L)].set(
        state["pending"].astype(jnp.int32), mode="drop"
    )
    chains, have = pld_lib.propose_device(
        ctx, jnp.minimum(n + 1, L), draft_k,
        max_ngram=max_ngram, min_ngram=min_ngram,
    )
    have = jnp.where(live, have, 0)
    chains = jnp.where(jnp.arange(draft_k)[None] < have[:, None], chains, 0)
    return ctx, chains, have


def _commit_ctx(ctx, n, acc_tok, n_acc):
    """Scatter this round's accepted tokens into the context buffer at
    positions [n, n + n_acc) — the device-side maintenance that keeps the
    next round's PLD exact without any host contexts."""
    B, L = ctx.shape
    T = acc_tok.shape[1]
    t_ids = jnp.arange(T)
    dest = jnp.where(
        t_ids[None, :] < n_acc[:, None], n[:, None] + t_ids[None, :], L
    )
    return ctx.at[jnp.arange(B)[:, None], dest].set(acc_tok, mode="drop")


def prefill_chunk_stage(
    cfg: ModelConfig,
    params: dict,
    cache: dict,
    state: dict,
    *,
    chunk: int,
    sampled: bool = False,
) -> Tuple[dict, dict]:
    """Continuous chunked prefill, fused into the serving round.

    Consumes up to ``chunk`` prompt tokens for every slot whose prompt is
    still being prefilled (``state["pf_done"] < state["pf_len"]``; the
    prompt itself sits in the carried ``ctx`` buffer) through ONE
    ``decode_step`` + ``commit_cache`` — the staged rows of a chunk are
    committed unconditionally (the prompt needs no verification), advancing
    ``cache["pos"]`` and ``pf_done`` together. The whole stage is
    ``lax.cond``-gated on any slot being mid-prefill, so steady-state
    rounds (nobody prefilling) skip its compute entirely while keeping one
    executable.

    On the round a slot finishes its prompt, its first generated token is
    produced HERE — greedy argmax of the last prompt position's logits, or
    (``sampled=True``) the same split + uniform + warp + inverse-CDF
    sequence the dense admission path runs on host — and stored as the
    slot's ``pending``, so the slot joins the decode round in the SAME
    dispatch and its token stream matches the dense path's from the first
    token. Slots still mid-prefill get their ``pending`` set to
    ``ctx[pos]`` (the prompt token already there), which turns the round
    prologue's pending scatter into a value no-op — the prompt is never
    corrupted, and the serving wrapper masks those slots out of ``live``
    for the decode half.

    Restrictions (enforced at server build time): attention-only stacks
    (SSM states would need per-slot zeroing at enqueue), non-ring paged
    caches, ``round_mode="single"``.
    """
    pf_done, pf_len = state["pf_done"], state["pf_len"]
    active = pf_done < pf_len

    def _run(ops):
        cache, state = ops
        state = dict(state)
        ctx = state["ctx"]
        B, L = ctx.shape
        pf_done, pf_len = state["pf_done"], state["pf_len"]
        n_new = jnp.where(
            active, jnp.minimum(pf_len - pf_done, chunk), 0
        ).astype(jnp.int32)
        offs = pf_done[:, None] + jnp.arange(chunk, dtype=jnp.int32)[None]
        toks = jnp.take_along_axis(ctx, jnp.clip(offs, 0, L - 1), axis=1)
        logits, staged = M.decode_step(cfg, params, cache, toks, q_pos=offs)
        path = jnp.broadcast_to(
            jnp.arange(chunk, dtype=jnp.int32)[None], (B, chunk)
        )
        new_cache = M.commit_cache(cfg, cache, staged, path, n_new)
        done_now = active & (pf_done + n_new >= pf_len)
        last_i = jnp.clip(n_new - 1, 0, chunk - 1)
        last = jnp.take_along_axis(logits, last_i[:, None, None], axis=1)[:, 0]
        if sampled:
            # device twin of the host admission draw (add_request): split
            # the admission-bound key, one uniform from the sub-key, warp,
            # inverse-CDF — and carry the advanced key only on completion
            ks = jax.vmap(lambda k: jax.random.split(k, 2))(state["key"])
            u0 = jax.vmap(lambda k: jax.random.uniform(k, ()))(ks[:, 1])
            q = verify_lib.sampling_probs(
                last, state["temp"], state["topk"], state["topp"]
            )
            first = verify_lib._inv_cdf(q, u0)
            state["key"] = jnp.where(
                done_now[:, None], ks[:, 0], state["key"]
            )
        else:
            first = jnp.argmax(last, -1).astype(jnp.int32)
        pend = jnp.where(done_now, first, state["pending"])
        new_done = pf_done + n_new
        still = new_done < pf_len
        safe = jnp.take_along_axis(
            ctx, jnp.clip(new_cache["pos"], 0, L - 1)[:, None], axis=1
        )[:, 0]
        state["pending"] = jnp.where(still, safe, pend).astype(jnp.int32)
        state["pf_done"] = new_done
        return new_cache, state

    return jax.lax.cond(
        jnp.any(active), _run, lambda ops: ops, (cache, dict(state))
    )


def chain_round(
    cfg: ModelConfig,
    params: dict,
    cache: dict,                      # donated: the commit aliases in place
    state: dict,                      # donated carried device state (see server)
    c: jax.Array,                     # () f32 draft cost coefficient
    gates: Optional[jax.Array],       # (num_layers,) DSIA layer gates or None
    *,
    draft_k: int,
    use_draft: bool,
    adaptive: bool,
    min_obs: int,
    t_min: float,
    draft_kv: str = "recompute",
    max_ngram: int = 4,
    min_ngram: int = 1,
    sampled: bool = False,
):
    """ONE fused, device-resident ``chain_fused`` serving round.

    PLD retrieval over the carried context buffer, Eq. 5 per-slot draft
    budgets from the carried Eq. 4 EMA state, the k-step neural chain scan,
    target verification, acceptance, cache + context commit, and the EMA
    update for round r+1 — all inside a single jitted dispatch, so the host
    never blocks between rounds (the pipelined server drains the returned
    ``out`` arrays whenever it chooses to sync).

    ``state`` carries ``pending (B,) i32``, ``live (B,) bool``,
    ``ctx (B, max_len) i32``, and the Eq. 4 estimator arrays ``alpha``,
    ``hist``, ``hist_n``, ``hist_ptr`` (see ``acceptance.ema_init``).
    With ``sampled=True`` it additionally carries the per-slot sampling
    state — ``key (B, 2) u32`` threefry keys plus ``temp``/``topk``/
    ``topp`` warp params — and verification becomes speculative SAMPLING
    acceptance (``verify_accept_commit_sampled``): the keys are split
    in-dispatch, the advanced keys ride the carried state, and slots with
    ``temp <= 0`` reproduce the greedy round token-for-token. Same
    executable count, zero extra host syncs.
    Returns ``(cache, state, out)`` where ``out`` holds the round's
    accepted tokens: ``acc (B, k+1)`` (valid prefix ``n_acc``), plus
    ``pld_have``/``have`` for host-side stats.

    On a mesh the carried state is pinned to its data-parallel placement
    on entry AND exit (see ``_pin_batch``): one donated dispatch per round
    stays one dispatch — no resharding round-trips between rounds.
    """
    dax = data_axis()
    state = _pin_batch(dict(state), dax)
    live = state["live"]
    pending = state["pending"]
    n = cache["pos"]
    with jax.named_scope("pld"):
        ctx, chains, have = _round_prologue(
            cfg, cache, state, draft_k, max_ngram, min_ngram
        )
    pld_have = have
    limit = jnp.zeros_like(have)
    if use_draft:
        if adaptive:
            budget = best_chain_length_batched(
                state["alpha"], c, draft_k, t_min
            )
            limit = jnp.where(state["hist_n"] >= min_obs, budget, draft_k)
        else:
            limit = jnp.full(live.shape, draft_k, jnp.int32)
        limit = jnp.where(live, limit, 0)

        def _draft(ops):
            ch, hv = ops
            return chain_draft_scan(
                cfg, draft_k, params, cache, pending, ch, hv, limit, gates,
                draft_kv=draft_kv,
            )

        # runtime skip: rounds where PLD covered every budget (or routing
        # stopped drafting) pay NO neural draft compute — the economics the
        # split path gets from its host-computed trip count, decided
        # entirely on device. (The scan keeps its static trip inside the
        # taken branch: XLA's known-trip While beats the dynamic-trip form
        # on CPU — see chain_draft_scan(dynamic_steps=...).)
        with jax.named_scope("draft"):
            chains, have = jax.lax.cond(
                jnp.any(limit > have), _draft, lambda ops: ops, (chains, have)
            )
    with jax.named_scope("verify"):
        if sampled:
            # live-gated key advance: a dead slot's stream is dead, and a
            # chunk-prefilling slot (serving's prefill_chunk wrapper masks it
            # out of `live`) must reach its first decode round with the exact
            # key admission bound — the same key the dense admission path
            # leaves it with. Live slots' uniforms are unchanged (per-slot
            # threefry streams are independent).
            new_key, u = verify_lib.round_uniforms(state["key"], draft_k + 1)
            state["key"] = jnp.where(live[:, None], new_key, state["key"])
            new_cache, n_chain, new_pending = verify_accept_commit_sampled(
                cfg, params, cache, pending, chains, have, live,
                state["temp"], state["topk"], state["topp"], u,
            )
        else:
            new_cache, nxt, n_chain, new_pending = verify_accept_commit(
                cfg, params, cache, pending, chains, have, live
            )
    with jax.named_scope("commit"):
        n_acc = jnp.where(live, n_chain + 1, 0).astype(jnp.int32)
        acc_tok = jnp.concatenate([pending[:, None], chains], axis=1)
        state["ctx"] = _commit_ctx(ctx, n, acc_tok, n_acc)
        state["pending"] = jnp.where(live, new_pending, pending).astype(jnp.int32)
        # Eq. 4 EMA over the NEURAL drafter: first neural position's outcome,
        # only when the PLD prefix was fully accepted (parent-accepted rule)
        obs = live & (have > pld_have) & (n_chain >= pld_have)
        outcome = (n_chain > pld_have).astype(jnp.float32)
        (state["alpha"], state["hist"], state["hist_n"],
         state["hist_ptr"]) = ema_update(
            state["alpha"], state["hist"], state["hist_n"], state["hist_ptr"],
            outcome, obs,
        )
        # per-slot round facts: the server's pipelined drain sums "drafted"
        # when it resolves the future, and the device telemetry accumulator
        # (serving/telemetry.py) folds the rest without any extra dispatch
        out = {
            "acc": acc_tok, "n_acc": n_acc,
            "drafted": jnp.maximum(have - pld_have, 0),
            "pld_have": pld_have, "budget": limit,
        }
    return new_cache, _pin_batch(state, dax), _pin_batch(out, dax)


def tree_round(
    cfg: ModelConfig,
    params: dict,
    cache: dict,                      # donated: the commit aliases in place
    state: dict,                      # donated carried device state (see server)
    c: jax.Array,                     # () f32 draft cost coefficient
    gates: Optional[jax.Array],       # (num_layers,) DSIA layer gates or None
    *,
    draft_k: int,
    expansions: int,
    top_k: int,
    top_p: float,
    bucket: int,
    pld_alpha: float,
    use_draft: bool,
    adaptive: bool,
    min_obs: int,
    t_min: float,
    draft_kv: str = "recompute",
    attn_backend: Optional[str] = None,
    max_ngram: int = 4,
    min_ngram: int = 1,
    sampled: bool = False,
):
    """ONE fused, device-resident ``tree_fused`` (DyTC §4.2) serving round:
    PLD retrieval + tree seeding + the expansion scan + target verify + the
    vectorized accepted-path walk + cache/context commit + the Eq. 4 EMA
    update, all in a single jitted dispatch. Same carried ``state`` contract
    (and the same entry/exit ``_pin_batch`` placement pins on a mesh)
    as ``chain_round``; ``out["acc"]`` holds the accepted path tokens.
    ``sampled=True`` swaps the greedy accepted-path walk for the stochastic
    tree walk (``tree_verify_accept_commit_sampled``) driven by carried
    per-slot keys/warp params — see ``chain_round``; same dispatch story,
    temp <= 0 slots stay token-identical to greedy."""
    dax = data_axis()
    state = _pin_batch(dict(state), dax)
    live = state["live"]
    pending = state["pending"]
    n = cache["pos"]
    B = live.shape[0]
    with jax.named_scope("pld"):
        ctx, chains, have = _round_prologue(
            cfg, cache, state, draft_k, max_ngram, min_ngram
        )
        tokens, parents, depth, p_acc, mask, count = tree_seed_device(
            pending, chains, have, bucket, pld_alpha
        )
    pld_have = have
    first_neural = jnp.full((B,), -1, jnp.int32)
    limits = jnp.zeros((B,), jnp.int32)
    if use_draft and expansions > 0:
        if adaptive:
            budget = best_tree_expansions_batched(
                state["alpha"], c, expansions, t_min
            )
            limits = jnp.where(state["hist_n"] >= min_obs, budget, expansions)
        else:
            limits = jnp.full((B,), expansions, jnp.int32)
        limits = jnp.where(live, limits, 0)

        def _grow(ops):
            tk, pr, dp, pa, mk, ct, fn = ops
            return tree_draft_scan(
                cfg, expansions, top_k, params, cache,
                tk, pr, dp, pa, mk, ct,
                limits, state["alpha"],
                jnp.maximum(c.astype(jnp.float32), 1e-3),
                jnp.asarray(t_min, jnp.float32), gates,
                top_p=top_p, draft_kv=draft_kv,
            )

        # runtime skip (see chain_round): PLD-only / routing-stopped rounds
        # pay no expansion compute inside the same executable
        with jax.named_scope("draft"):
            tokens, parents, depth, p_acc, mask, count, first_neural = (
                jax.lax.cond(
                    jnp.any(limits > 0), _grow, lambda ops: ops,
                    (tokens, parents, depth, p_acc, mask, count,
                     first_neural),
                )
            )
    with jax.named_scope("verify"):
        if sampled:
            # live-gated key advance — see chain_round: frozen keys for dead
            # and chunk-prefilling slots, identical uniforms for live ones
            new_key, u = verify_lib.round_uniforms(state["key"], bucket)
            state["key"] = jnp.where(live[:, None], new_key, state["key"])
            new_cache, path, n_acc, bonus = tree_verify_accept_commit_sampled(
                cfg, params, cache, tokens, parents, depth, mask, count, live,
                state["temp"], state["topk"], state["topp"], u,
                attn_backend=attn_backend,
            )
        else:
            new_cache, path, n_acc, bonus = tree_verify_accept_commit(
                cfg, params, cache, tokens, parents, depth, mask, count, live,
                attn_backend=attn_backend,
            )
    with jax.named_scope("commit"):
        acc_tok = jnp.take_along_axis(tokens, path, axis=1)          # (B, N)
        state["ctx"] = _commit_ctx(ctx, n, acc_tok, n_acc)
        state["pending"] = jnp.where(live, bonus, pending).astype(jnp.int32)
        # Eq. 4 EMA at the slot's first NEURAL node (parent-accepted rule; the
        # same bookkeeping the split round does on host after draining)
        N = tokens.shape[1]
        t_ids = jnp.arange(N)
        acc_mask = jnp.zeros((B, N), bool).at[
            jnp.arange(B)[:, None],
            jnp.where(t_ids[None, :] < n_acc[:, None], path, N),
        ].set(True, mode="drop")
        fn_c = jnp.clip(first_neural, 0, N - 1)
        fn_parent = jnp.take_along_axis(parents, fn_c[:, None], 1)[:, 0]
        parent_ok = jnp.take_along_axis(
            acc_mask, jnp.clip(fn_parent, 0, N - 1)[:, None], 1
        )[:, 0]
        obs = live & (first_neural >= 0) & (fn_parent >= 0) & parent_ok
        outcome = jnp.take_along_axis(acc_mask, fn_c[:, None], 1)[:, 0]
        (state["alpha"], state["hist"], state["hist_n"],
         state["hist_ptr"]) = ema_update(
            state["alpha"], state["hist"], state["hist_n"], state["hist_ptr"],
            outcome.astype(jnp.float32), obs,
        )
        # per-slot round facts (see chain_round): drained sums + telemetry
        # accumulation happen downstream, inside the same executable or on
        # already-resolved futures — never as an extra dispatch
        out = {
            "acc": acc_tok, "n_acc": n_acc,
            "drafted": jnp.clip(count - pld_have - 1, 0, None),
            "pld_have": pld_have, "budget": limits,
        }
    return new_cache, _pin_batch(state, dax), _pin_batch(out, dax)


class SpecEngine:
    """Single-sequence (B=1) speculative engine; the batched path lives in
    repro.serving.server."""

    def __init__(
        self,
        cfg: ModelConfig,
        params: dict,
        max_len: int = 2048,
        draft_exec: str = "auto",          # auto | slice | mask
        greedy: bool = True,
    ):
        self.cfg = cfg
        self.params = params
        self.max_len = max_len
        self.greedy = greedy
        segs = M.layout(cfg)
        homogeneous = len(segs) == 1 and len(segs[0].unit) == 1
        if draft_exec == "auto":
            draft_exec = "slice" if homogeneous else "mask"
        if draft_exec == "slice" and not homogeneous:
            raise ValueError("slice exec requires a homogeneous layer stack")
        self.draft_exec = draft_exec
        self.pld = PromptLookup()
        self.acceptance = AcceptanceTracker()
        self.costs = CostTracker()

        self._variants: Dict[str, Tuple[ModelConfig, dict, Optional[np.ndarray]]] = {
            "full": (cfg, params, None)
        }
        self._spec_by_name: Dict[str, DraftSpec] = {}
        self._decode_fns: Dict[Tuple[str, int], Callable] = {}
        self._commit_fns: Dict[int, Callable] = {}
        self._prefill_fn = jax.jit(
            functools.partial(M.prefill, cfg), static_argnames=()
        )
        # runtime state
        self.cache: Optional[dict] = None
        self.tokens: List[int] = []
        self.pending: Optional[int] = None
        self.stats = {"target_calls": 0, "draft_calls": 0, "rounds": 0,
                      "accepted_tokens": 0, "draft_time": 0.0, "verify_time": 0.0,
                      "modeled_draft_cost": 0.0}

    # ------------------------------------------------------------- variants
    def register_draft(self, spec: DraftSpec) -> None:
        if spec.kind == "retrieval" or spec.name in self._variants:
            self.acceptance.set_prior(spec.name, spec.prior_alpha)
            self.costs.set_prior(spec.name, spec.prior_c)
            return
        cfg, params = self.cfg, self.params
        gates = spec.gates_array(self.cfg.num_layers)
        if spec.quantize == "int8":
            params = fake_quant_int8(params)
        if self.draft_exec == "slice" and spec.gates is not None:
            kept = np.flatnonzero(gates > 0)
            cfg = dataclasses.replace(cfg, num_layers=len(kept))
            seg = params["segments"][0]
            params = dict(params)
            params["segments"] = [jax.tree.map(lambda a: a[kept], seg)]
            gates_arr = None
        else:
            gates_arr = gates
        self._variants[spec.name] = (cfg, params, gates_arr)
        self.acceptance.set_prior(spec.name, spec.prior_alpha)
        self.costs.set_prior(spec.name, spec.prior_c)
        self._spec_by_name[spec.name] = spec

    def _slice_cache(self, variant: str) -> dict:
        cfg_v, _, _ = self._variants[variant]
        if variant == "full" or self.draft_exec != "slice" or cfg_v.num_layers == self.cfg.num_layers:
            return self.cache
        spec = self._spec_by_name[variant]
        kept = np.flatnonzero(spec.gates_array(self.cfg.num_layers) > 0)
        seg = self.cache["segments"][0]
        return {
            "pos": self.cache["pos"],
            "segments": [jax.tree.map(lambda a: a[kept], seg)],
        }

    # --------------------------------------------------------------- jitting
    def _decode_fn(self, variant: str, bucket: int) -> Callable:
        key = (variant, bucket)
        if key in self._decode_fns:
            return self._decode_fns[key]
        cfg_v, params_v, gates = self._variants[variant]
        spec = getattr(self, "_spec_by_name", {}).get(variant)
        override = None
        if spec is not None and spec.attn_override is not None:
            kind, window, sink = spec.attn_override
            override = {"kind": kind, "window": window, "sink": sink}

        @jax.jit
        def fn(params, cache, tokens, tmask, qpos, gates_arr):
            return M.decode_step(
                cfg_v, params, cache, tokens,
                gates=gates_arr, tree_mask=tmask, q_pos=qpos,
                attn_override=override,
            )

        self._decode_fns[key] = (fn, params_v, gates)
        return self._decode_fns[key]

    def _commit_fn(self, bucket: int) -> Callable:
        if bucket not in self._commit_fns:
            self._commit_fns[bucket] = jax.jit(
                functools.partial(M.commit_cache, self.cfg)
            )
        return self._commit_fns[bucket]

    # ---------------------------------------------------------------- runtime
    def start(self, prompt: np.ndarray) -> None:
        prompt = np.asarray(prompt, np.int32)
        self.cache = M.init_cache(self.cfg, 1, self.max_len, dtype=jnp.dtype(self.cfg.dtype))
        t0 = time.perf_counter()
        last, self.cache = jax.block_until_ready(
            self._prefill_fn(self.params, {"tokens": jnp.asarray(prompt[None])}, self.cache)
        )
        self.costs.observe_target(time.perf_counter() - t0, tokens=max(len(prompt), 1))
        self.tokens = [int(t) for t in prompt]
        self.pending = int(np.argmax(np.asarray(last)[0]))

    @property
    def context(self) -> np.ndarray:
        return np.asarray(self.tokens + [self.pending], np.int32)

    def _run_nodes(
        self,
        variant: str,
        tokens: np.ndarray,     # (n,)
        rel_pos: np.ndarray,    # (n,)
        mask: np.ndarray,       # (n, n)
    ):
        n = len(tokens)
        T = bucket_for(n)
        toks = np.zeros(T, np.int32)
        toks[:n] = tokens
        rel = np.zeros(T, np.int32)
        rel[:n] = rel_pos
        rel[n:] = (rel_pos.max() if n else 0) + 1 + np.arange(T - n)
        m = np.eye(T, dtype=bool)
        m[:n, :n] = mask
        fn, params_v, gates = self._decode_fn(variant, T)
        cache = self._slice_cache(variant)
        qpos = jnp.asarray(self.cache["pos"] + jnp.asarray(rel))
        logits, staged = fn(
            params_v, cache, jnp.asarray(toks[None]), jnp.asarray(m), qpos,
            None if gates is None else jnp.asarray(gates),
        )
        return logits, staged, T

    # draft call: logits for a node set under a draft config (stage-only)
    def draft_logits(self, spec_name: str, tokens, rel_pos, mask) -> np.ndarray:
        t0 = time.perf_counter()
        logits, _, _ = self._run_nodes(spec_name, tokens, rel_pos, mask)
        logits = np.asarray(jax.block_until_ready(logits))[0]
        dt = time.perf_counter() - t0
        self.stats["draft_calls"] += 1
        self.stats["draft_time"] += dt
        # modeled TPU cost: one target-forward-equivalent x the DSIA cost
        # coefficient per draft call (a KV-cached draft computes ~1 new
        # token per call; chain recomputation is a CPU-engine artifact)
        spec = self._spec_by_name.get(spec_name)
        self.stats["modeled_draft_cost"] += spec.prior_c if spec else 0.5
        self.costs.observe(spec_name, dt, tokens=len(tokens))
        return logits[: len(tokens)]

    # verification: full model over the tree, then commit the accepted path
    def verify_and_commit(self, tree: DraftTree) -> List[int]:
        tokens, rel, mask, real = tree.flatten()
        n = len(tree)
        t0 = time.perf_counter()
        logits, staged, T = self._run_nodes("full", tokens[:n], rel[:n], mask[:n, :n])
        logits = np.asarray(jax.block_until_ready(logits))[0]   # (T, V)
        self.stats["verify_time"] += time.perf_counter() - t0
        self.stats["target_calls"] += 1
        self.costs.observe_target(time.perf_counter() - t0, tokens=1)
        next_argmax = np.argmax(logits[:n], axis=-1)
        path, bonus = verify_lib.greedy_accept_tree(tree, next_argmax)

        # commit: accepted nodes' staged KV/states, in path order
        T_pad = bucket_for(n)
        path_idx = np.zeros(T_pad, np.int32)
        path_idx[: len(path)] = path
        commit = self._commit_fn(T_pad)
        self.cache = commit(
            self.cache, staged, jnp.asarray(path_idx), jnp.asarray(len(path), jnp.int32)
        )
        accepted = [tree.tokens[i] for i in path]
        self.tokens.extend(accepted)
        self.pending = int(bonus)
        self.stats["rounds"] += 1
        self.stats["accepted_tokens"] += len(accepted)
        return accepted

    # ------------------------------------------------------------ baselines
    def ar_step(self) -> int:
        """Plain autoregressive: verify a root-only tree (1 token/step)."""
        tree = DraftTree(self.pending)
        self.verify_and_commit(tree)
        return self.tokens[-1]

    def generate_ar(self, n_tokens: int) -> List[int]:
        out = []
        while len(out) < n_tokens:
            self.ar_step()
            out.append(self.tokens[-1])
        return out[:n_tokens]
