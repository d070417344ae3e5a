"""Decoder assembly: segment layout, init, train/prefill/decode, commit.

Execution model
---------------
Layers are grouped into *segments* of repeated identical units so the stack
lowers as ``lax.scan`` over repeats (compile-time friendly for 56-layer
models) with the unit unrolled inside the body. Homogeneous models have
unit=1; gemma3 has unit=6 (5 local + 1 global); jamba unit=8 (7 mamba + 1
attn, MoE every other layer).

DSIA layer gating
-----------------
Every entry point takes ``gates`` — a float (num_layers,) vector. A gated-off
layer (gate=0) contributes nothing to the residual stream and its staged
KV/state is ignored at commit. This is how layer-sparsity and early-exit
draft models are expressed *in the same compiled executable* (``mask`` mode).
``slice_params`` additionally materializes a reduced-depth param pytree for a
fixed skip set (``slice`` mode — fewer FLOPs, one compile per draft config).

Cache semantics: stage-then-commit
----------------------------------
``decode_step`` NEVER writes the cache: it returns logits plus per-layer
staged K/V (and per-step SSM states). After verification the engine calls
``commit_cache`` with the accepted path; rejected drafts leave no trace.
This is what makes speculative verification lossless and rollback-free, and
it is ring-buffer safe for sliding-window layers.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config.base import AttentionKind, BlockKind, ModelConfig
from repro.kernels.int8_matmul import quantize_weight
from repro.models import attention as attn_lib
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models.layers import apply_rope, embed_tokens, mlp_apply, mlp_init, rms_norm, unembed
from repro.models.shard_utils import constrain, data_axis

Cache = Dict[str, Any]


# ===================================================================== layout
@dataclasses.dataclass(frozen=True)
class LayerSpec:
    block: BlockKind
    attn: AttentionKind
    is_moe: bool
    has_mlp: bool


@dataclasses.dataclass(frozen=True)
class Segment:
    start: int                       # first layer index
    repeats: int
    unit: Tuple[LayerSpec, ...]

    @property
    def num_layers(self) -> int:
        return self.repeats * len(self.unit)


def _layer_spec(cfg: ModelConfig, i: int) -> LayerSpec:
    return LayerSpec(
        block=cfg.block_kind(i),
        attn=cfg.attention_kind(i),
        is_moe=cfg.is_moe_layer(i) and cfg.has_mlp(i),
        has_mlp=cfg.has_mlp(i),
    )


def layout(cfg: ModelConfig) -> List[Segment]:
    """Partition layers into scan segments of repeated units."""
    specs = [_layer_spec(cfg, i) for i in range(cfg.num_layers)]
    n = cfg.num_layers
    # find the smallest unit size that tiles the prefix
    for u in range(1, n + 1):
        if all(specs[i] == specs[i % u] for i in range(n - n % u)):
            reps = n // u
            segs = [Segment(0, reps, tuple(specs[:u]))]
            if n % u:
                segs.append(Segment(reps * u, 1, tuple(specs[reps * u :])))
            return segs
    return [Segment(0, 1, tuple(specs))]


# ======================================================================= init
def _attn_init(key, cfg: ModelConfig, dtype) -> dict:
    d, H, KV = cfg.d_model, cfg.num_heads, cfg.num_kv_heads
    hd = cfg.resolved_head_dim()
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s = d ** -0.5
    so = (H * hd) ** -0.5
    return {
        "wq": (jax.random.normal(k1, (d, H, hd)) * s).astype(dtype),
        "wk": (jax.random.normal(k2, (d, KV, hd)) * s).astype(dtype),
        "wv": (jax.random.normal(k3, (d, KV, hd)) * s).astype(dtype),
        "wo": (jax.random.normal(k4, (H, hd, d)) * so).astype(dtype),
    }


def _layer_init(key, cfg: ModelConfig, spec: LayerSpec, dtype) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    p: dict = {"norm1": jnp.zeros((cfg.d_model,), dtype)}
    if spec.block is BlockKind.ATTENTION:
        p["attn"] = _attn_init(k1, cfg, dtype)
    else:
        p["mamba"] = ssm_lib.ssm_init(k1, cfg.d_model, cfg.ssm, dtype)
    if spec.has_mlp:
        p["norm2"] = jnp.zeros((cfg.d_model,), dtype)
        if spec.is_moe:
            p["moe"] = moe_lib.moe_init(k2, cfg.d_model, cfg.moe, cfg.mlp_gated, dtype)
        else:
            p["mlp"] = mlp_init(k2, cfg.d_model, cfg.d_ff, cfg.mlp_gated, dtype)
    return p


def init_params(cfg: ModelConfig, key: jax.Array) -> dict:
    dtype = jnp.dtype(cfg.dtype)
    keys = jax.random.split(key, 4 + len(layout(cfg)))
    d, V = cfg.d_model, cfg.padded_vocab
    nc = max(cfg.num_codebooks, 1)
    embed_shape = (nc, V, d) if cfg.num_codebooks else (V, d)
    params: dict = {
        "embed": (jax.random.normal(keys[0], embed_shape) * d ** -0.5).astype(dtype),
        "final_norm": jnp.zeros((d,), dtype),
    }
    if not cfg.tie_embeddings:
        head_shape = (nc, d, V) if cfg.num_codebooks else (d, V)
        params["lm_head"] = (
            jax.random.normal(keys[1], head_shape) * d ** -0.5
        ).astype(dtype)
    segs = []
    for si, seg in enumerate(layout(cfg)):
        seg_keys = jax.random.split(keys[3 + si], seg.repeats * len(seg.unit)).reshape(
            (seg.repeats, len(seg.unit)) + keys.shape[1:]
        )

        def init_repeat(ks, _unit=seg.unit):
            return [
                _layer_init(ks[u], cfg, spec, dtype) for u, spec in enumerate(_unit)
            ]

        segs.append(jax.vmap(init_repeat)(seg_keys))
    params["segments"] = segs
    return params


def map_mlp_weights(params: dict, fn) -> dict:
    """``params`` with ``fn`` applied to every dense-MLP weight (each a
    stacked (R, K, N) leaf of a segment); every other leaf is the same
    object. MoE experts are no dense MLP and stay as they are."""
    def layer(p):
        if "mlp" not in p:
            return p
        return {**p, "mlp": {k: fn(w) for k, w in p["mlp"].items()}}

    return {**params,
            "segments": [[layer(p) for p in seg] for seg in params["segments"]]}


def int8_mlp_params(params: dict) -> dict:
    """The W8A8 draft level's params: every dense-MLP weight replaced by its
    int8 image (``quantize_weight``: per layer and column over the whole
    K); every other leaf is the target's own object."""
    return map_mlp_weights(params, quantize_weight)


# ====================================================================== cache
def pages_for(max_len: int, page_size: int) -> int:
    """Pages spanning ``max_len`` tokens (the page-table width per slot)."""
    return -(-max_len // page_size)


def init_cache(
    cfg: ModelConfig,
    batch: int,
    max_len: int,
    *,
    ring_window: bool = False,
    dtype=None,
    paged: bool = False,
    page_size: int = 64,
    num_pages: Optional[int] = None,
) -> Cache:
    """Allocate a committed cache. ``ring_window`` stores only `sliding_window`
    slots (ring buffer) for sliding layers — required for long_500k.

    ``paged=True`` replaces the dense per-slot ``(B, max_len)`` attention
    buffers with one SHARED page pool per layer — ``k_pages``/``v_pages``
    of shape ``(repeats, num_pages, page_size, KV, hd)`` — plus a top-level
    per-slot int32 ``page_table`` of shape ``(batch, max_len // page_size)``
    mapping logical page index -> pool page (-1 = unallocated). Every read
    and write addresses through the table (decode gathers a dense per-slot
    view; write_slot/commit_cache scatter through it), so attention output
    is BIT-identical to the dense cache: garbage in unallocated pages and
    beyond ``pos`` is killed by the same ``kv_pos`` masking that already
    handles partially-filled tails. SSM per-slot states are O(1) and stay
    dense. ``num_pages`` defaults to a full allocation (batch * pages per
    slot); callers that size requests can shrink it. Rings page nothing:
    ``ring_window`` + ``paged`` is rejected."""
    dtype = dtype or jnp.dtype(cfg.dtype)
    hd = cfg.resolved_head_dim()
    if paged:
        if ring_window:
            raise ValueError("paged caches do not support ring_window")
        if max_len % page_size:
            raise ValueError(
                f"max_len={max_len} must be a multiple of page_size={page_size}"
            )
        if num_pages is None:
            num_pages = batch * pages_for(max_len, page_size)
    segs = []
    for seg in layout(cfg):
        unit_caches = []
        for spec in seg.unit:
            if spec.block is BlockKind.ATTENTION:
                if paged:
                    unit_caches.append(
                        {
                            "k_pages": jnp.zeros(
                                (seg.repeats, num_pages, page_size, cfg.num_kv_heads, hd),
                                dtype,
                            ),
                            "v_pages": jnp.zeros(
                                (seg.repeats, num_pages, page_size, cfg.num_kv_heads, hd),
                                dtype,
                            ),
                        }
                    )
                    continue
                S_c = (
                    min(cfg.sliding_window, max_len)
                    if (ring_window and spec.attn is AttentionKind.SLIDING)
                    else max_len
                )
                unit_caches.append(
                    {
                        "k": jnp.zeros((seg.repeats, batch, S_c, cfg.num_kv_heads, hd), dtype),
                        "v": jnp.zeros((seg.repeats, batch, S_c, cfg.num_kv_heads, hd), dtype),
                    }
                )
            else:
                s = cfg.ssm
                nh = s.num_heads(cfg.d_model)
                din = s.d_inner(cfg.d_model)
                gds = s.ngroups * s.d_state
                R, K = seg.repeats, s.d_conv
                unit_caches.append(
                    {
                        "ssm": jnp.zeros((R, batch, nh, s.head_dim, s.d_state), jnp.float32),
                        "conv_x": jnp.zeros((R, batch, K - 1, din), dtype),
                        "conv_B": jnp.zeros((R, batch, K - 1, gds), dtype),
                        "conv_C": jnp.zeros((R, batch, K - 1, gds), dtype),
                    }
                )
        segs.append(unit_caches)
    out: Cache = {"pos": jnp.zeros((batch,), jnp.int32), "segments": segs}
    if paged:
        out["page_table"] = jnp.full(
            (batch, pages_for(max_len, page_size)), -1, jnp.int32
        )
    return out


# ================================================================ layer bodies
def _attn_layer(
    cfg: ModelConfig,
    p: dict,
    spec: LayerSpec,
    h: jax.Array,                  # (B, T, d)
    q_pos: jax.Array,              # (T,)
    mode: str,
    layer_cache: Optional[dict],
    tree_mask: Optional[jax.Array],
    gate: jax.Array,
    attn_override: Optional[dict] = None,   # {"kind","window","sink"} DSIA
    seq_axes: Optional[tuple] = None,       # context-parallel decode partials
    attn_backend: Optional[str] = None,     # "pallas": kernel tree-verify pass
    staged_buf: Optional[dict] = None,      # {"k","v"} carried draft KV block
    staged_pos: Optional[jax.Array] = None,
    staged_mask: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Optional[dict]]:
    """Returns (residual delta, staged/new cache entries)."""
    B, T, _ = h.shape
    hd = cfg.resolved_head_dim()
    x = rms_norm(h, p["norm1"], cfg.norm_eps)
    # pin weights to their TP spec at the use site: FSDP-stored weights get
    # all-gathered over 'data' here (small), instead of GSPMD gathering the
    # activations (measured 1.6 GiB/layer on mixtral prefill)
    from repro.models.shard_utils import attention_head_policy, constrain_full

    pol = attention_head_policy(cfg.num_heads, cfg.num_kv_heads)
    qh = "model" if pol in ("kv", "q") else None
    kh = "model" if pol == "kv" else None
    wq = constrain_full(p["attn"]["wq"], None, qh, None)
    wk = constrain_full(p["attn"]["wk"], None, kh, None)
    wv = constrain_full(p["attn"]["wv"], None, kh, None)
    wo = constrain_full(p["attn"]["wo"], qh, None, None)
    q = jnp.einsum("btd,dhk->bthk", x, wq)
    k = jnp.einsum("btd,dgk->btgk", x, wk)
    v = jnp.einsum("btd,dgk->btgk", x, wv)
    rope_pos = q_pos[None, :] if q_pos.ndim == 1 else q_pos   # (B, T)
    q = apply_rope(q, rope_pos, cfg.rope_theta)
    k = apply_rope(k, rope_pos, cfg.rope_theta)

    kind = {
        AttentionKind.FULL: "causal",
        AttentionKind.SLIDING: "window",
    }[spec.attn]
    window = cfg.sliding_window
    sink = 0
    if attn_override is not None and spec.attn is AttentionKind.FULL:
        # Efficient-attention DSIA (StreamingLLM-style) applies to full-attn
        # layers only; sliding layers are already windowed.
        kind = attn_override["kind"]
        window = attn_override["window"]
        sink = attn_override.get("sink", 0)

    if mode in ("train", "prefill"):
        # pin attention inputs batch-sharded/model-replicated: the cache's
        # seq-sharded output spec otherwise back-propagates into k/v and the
        # blockwise kv-chunk scan gathers every chunk across the mesh
        from repro.models.shard_utils import data_axis as _dax
        k_a = constrain(k, _dax(), None, None, None)
        v_a = constrain(v, _dax(), None, None, None)
        q_a = constrain(q, _dax(), None, None, None)
        o = attn_lib.blockwise_attention(
            q_a, k_a, v_a, q_pos, q_pos, kind=kind, window=window,
            chunk_q=min(512, T), chunk_kv=min(1024, T),
        )
        staged = {"k": k, "v": v} if mode == "prefill" else None
    else:
        if "k_pages" in layer_cache:
            # block-paged cache: gather the slot's pages into a dense
            # (B, n_pp * P, KV, hd) view and run the unchanged decode path.
            # Unallocated pages (table -1, clamped to page 0) and rows past
            # ``pos`` hold garbage VALUES only — the kv_pos rule
            # (slot < pos) masks them to NEG_INF before the softmax, so the
            # output is bit-identical to the dense cache.
            tbl = layer_cache["_table"]                  # (B, n_pp)
            pool_k, pool_v = layer_cache["k_pages"], layer_cache["v_pages"]
            NP, P_sz = pool_k.shape[0], pool_k.shape[1]
            safe = jnp.clip(tbl, 0, NP - 1)
            Bt, n_pp = tbl.shape
            k_view = jnp.take(pool_k, safe, axis=0).reshape(
                Bt, n_pp * P_sz, pool_k.shape[2], pool_k.shape[3]
            )
            v_view = jnp.take(pool_v, safe, axis=0).reshape(
                Bt, n_pp * P_sz, pool_v.shape[2], pool_v.shape[3]
            )
            cache_kv = (k_view, v_view)
            ring = False
        else:
            S_c = layer_cache["k"].shape[2]
            # ring iff the allocation is capped at the window (see init_cache)
            ring = spec.attn is AttentionKind.SLIDING and S_c <= window
            cache_kv = (layer_cache["k"], layer_cache["v"])
        o = attn_lib.decode_attention(
            q,
            cache_kv[0],
            cache_kv[1],
            layer_cache["_pos"],
            k,
            v,
            q_pos,
            tree_mask=tree_mask,
            kind=kind,
            window=window,
            sink=sink,
            ring=bool(ring),
            chunk_kv=4096,
            seq_axes=None if ring else seq_axes,    # ring caches are small
            backend=attn_backend,
            k_staged=None if staged_buf is None else staged_buf["k"],
            v_staged=None if staged_buf is None else staged_buf["v"],
            staged_pos=staged_pos,
            staged_mask=staged_mask,
        )
        staged = {"k": k, "v": v}
    out = jnp.einsum("bthk,hkd->btd", o, wo)
    return out * gate, staged


def _mamba_layer(
    cfg: ModelConfig,
    p: dict,
    h: jax.Array,
    mode: str,
    layer_cache: Optional[dict],
    gate: jax.Array,
) -> Tuple[jax.Array, Optional[dict]]:
    B, T, _ = h.shape
    s = cfg.ssm
    x = rms_norm(h, p["norm1"], cfg.norm_eps)
    if layer_cache is None:  # train: fresh zero state
        nh = s.num_heads(cfg.d_model)
        din = s.d_inner(cfg.d_model)
        gds = s.ngroups * s.d_state
        layer_cache = {
            "ssm": jnp.zeros((B, nh, s.head_dim, s.d_state), jnp.float32),
            "conv_x": jnp.zeros((B, s.d_conv - 1, din), x.dtype),
            "conv_B": jnp.zeros((B, s.d_conv - 1, gds), x.dtype),
            "conv_C": jnp.zeros((B, s.d_conv - 1, gds), x.dtype),
        }
    out, new_cache, staged = ssm_lib.mamba_forward(
        p["mamba"], x, cfg.d_model, s, layer_cache, mode=mode,
    )
    if mode == "train":
        staged = None
    return out * gate, staged


def _mlp_layer(
    cfg: ModelConfig, p: dict, spec: LayerSpec, h, gate, aux_sum, mode: str,
    quantize=None,
):
    x = rms_norm(h, p["norm2"], cfg.norm_eps)
    if spec.is_moe:
        if mode == "train":
            moe_mode = "train"
        elif mode == "prefill" and not cfg.moe.prefill_dropless:
            moe_mode = "infer_grouped"     # TPU prefill: sharded capacity path
        else:
            moe_mode = "infer"             # dropless — batch-invariant decode
        # expert matmuls stay in the model dtype: the ActivationQuant DSIA
        # quantizes the dense-MLP hot path only (see docs/cascade.md)
        y, aux = moe_lib.moe_apply(
            p["moe"], x, cfg.moe, cfg.act, cfg.mlp_gated, mode=moe_mode,
        )
        aux_sum = aux_sum + aux["load_balance"] + aux["router_z"]
    else:
        y = mlp_apply(p["mlp"], x, cfg.act, cfg.mlp_gated, quantize=quantize)
    return y * gate, aux_sum


# ================================================================== traversal
def _run_stack(
    cfg: ModelConfig,
    params: dict,
    h: jax.Array,
    *,
    mode: str,
    cache: Optional[Cache],
    gates: Optional[jax.Array],
    q_pos: jax.Array,
    tree_mask: Optional[jax.Array],
    remat: bool = False,
    attn_override: Optional[dict] = None,
    seq_axes: Optional[tuple] = None,
    attn_backend: Optional[str] = None,
    quantize: Optional[str] = None,
    staged_kv: Optional[Any] = None,        # carried draft-KV segments (decode)
    staged_pos: Optional[jax.Array] = None,
    staged_mask: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Any, jax.Array]:
    """Returns (hidden, staged_or_new_cache_segments, moe_aux_sum)."""
    segs = layout(cfg)
    if gates is None:
        gates = jnp.ones((cfg.num_layers,), h.dtype)
    gates = gates.astype(h.dtype)
    cache_pos = cache["pos"] if cache is not None else jnp.zeros((), jnp.int32)
    # paged caches: the per-slot page table is closed over (like cache_pos),
    # NOT scanned — every layer of a segment shares the one (B, n_pp) table
    page_table = cache.get("page_table") if cache is not None else None

    staged_segments = []
    aux = jnp.zeros((), jnp.float32)

    for si, seg in enumerate(segs):
        U = seg.repeats * len(seg.unit)
        g_seg = jax.lax.dynamic_slice(gates, (seg.start,), (U,)).reshape(
            seg.repeats, len(seg.unit)
        )
        p_seg = params["segments"][si]
        c_seg = cache["segments"][si] if cache is not None else None
        s_seg = staged_kv[si] if staged_kv is not None else None

        def body(carry, xs, _unit=seg.unit):
            hh, aux_c = carry
            hh = constrain(hh, data_axis(), None, None)   # keep batch sharded
            p_u, g_u, c_u, s_u = xs
            staged_u = []
            for u, spec in enumerate(_unit):
                p_l = p_u[u]
                lc = None
                if c_u is not None:
                    lc = dict(c_u[u])
                    lc["_pos"] = cache_pos
                    if page_table is not None:
                        lc["_table"] = page_table
                gate = g_u[u]
                if spec.block is BlockKind.ATTENTION:
                    delta, staged = _attn_layer(
                        cfg, p_l, spec, hh, q_pos, mode, lc, tree_mask, gate,
                        attn_override, seq_axes, attn_backend,
                        staged_buf=None if s_u is None else s_u[u],
                        staged_pos=staged_pos, staged_mask=staged_mask,
                    )
                else:
                    delta, staged = _mamba_layer(cfg, p_l, hh, mode, lc, gate)
                hh = hh + delta
                if spec.has_mlp:
                    delta2, aux_c = _mlp_layer(
                        cfg, p_l, spec, hh, gate, aux_c, mode, quantize
                    )
                    hh = hh + delta2
                staged_u.append(staged)
            return (hh, aux_c), tuple(staged_u)

        body_fn = jax.checkpoint(body) if remat else body
        if seg.repeats == 1:
            (h, aux), staged = body_fn(
                (h, aux),
                (
                    jax.tree.map(lambda a: a[0], p_seg),
                    g_seg[0],
                    jax.tree.map(lambda a: a[0], c_seg) if c_seg is not None else None,
                    jax.tree.map(lambda a: a[0], s_seg) if s_seg is not None else None,
                ),
            )
            staged = jax.tree.map(lambda a: a[None], staged)
        else:
            (h, aux), staged = jax.lax.scan(
                body_fn, (h, aux), (p_seg, g_seg, c_seg, s_seg)
            )
        staged_segments.append(staged)
    return h, staged_segments, aux


def _embed(cfg: ModelConfig, params: dict, batch: Dict[str, jax.Array]) -> jax.Array:
    tokens = batch["tokens"]
    if cfg.num_codebooks:
        # (B, S, nc) codec tokens -> sum of per-codebook embeddings
        e = sum(
            embed_tokens(params["embed"][c], tokens[..., c])
            for c in range(cfg.num_codebooks)
        )
    else:
        e = embed_tokens(params["embed"], tokens)
    if cfg.num_image_tokens and "image_embeds" in batch:
        # VLM stub: splice precomputed patch embeddings where image_mask=1
        mask = batch["image_mask"][..., None].astype(e.dtype)
        img = batch["image_embeds"].astype(e.dtype)
        B, S, d = e.shape
        Ti = img.shape[1]
        pad = jnp.zeros((B, S - Ti, d), e.dtype)
        img_full = jnp.concatenate([img, pad], axis=1)
        # image tokens occupy the first Ti aligned slots marked by the mask
        e = e * (1 - mask) + img_full * mask
    return e


def _head(cfg: ModelConfig, params: dict, h: jax.Array) -> jax.Array:
    h = constrain(h, data_axis(), None, None)
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    if cfg.num_codebooks:
        if cfg.tie_embeddings:
            heads = jnp.swapaxes(params["embed"], 1, 2)    # (nc, d, V)
        else:
            heads = params["lm_head"]
        logits = jnp.einsum(
            "btd,cdv->btcv", h.astype(jnp.float32), heads.astype(jnp.float32)
        )
    else:
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        logits = unembed(h, head)
    if cfg.padded_vocab != cfg.vocab_size:
        ids = jnp.arange(cfg.padded_vocab)
        logits = jnp.where(ids < cfg.vocab_size, logits, -1e30)
    return logits


# =============================================================== entry points
def forward_train(
    cfg: ModelConfig,
    params: dict,
    batch: Dict[str, jax.Array],
    *,
    gates: Optional[jax.Array] = None,
    remat: bool = True,
) -> Tuple[jax.Array, jax.Array]:
    """Full causal forward. Returns (logits (B,S,[nc,]V) f32, moe_aux)."""
    h = _embed(cfg, params, batch)
    S = h.shape[1]
    q_pos = jnp.arange(S, dtype=jnp.int32)
    h, _, aux = _run_stack(
        cfg, params, h, mode="train", cache=None, gates=gates,
        q_pos=q_pos, tree_mask=None, remat=remat,
    )
    return _head(cfg, params, h), aux


def prefill(
    cfg: ModelConfig,
    params: dict,
    batch: Dict[str, jax.Array],
    cache: Cache,
    *,
    gates: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Cache]:
    """Process the prompt, fill the cache. Returns (last-token logits, cache)."""
    h = _embed(cfg, params, batch)
    B, S, _ = h.shape
    q_pos = jnp.arange(S, dtype=jnp.int32)
    h, staged, _ = _run_stack(
        cfg, params, h, mode="prefill", cache=cache, gates=gates,
        q_pos=q_pos, tree_mask=None,
    )
    new_cache = _write_prefill(cfg, cache, staged, S)
    logits = _head(cfg, params, h[:, -1:])
    return logits[:, 0], new_cache


def _write_prefill(cfg: ModelConfig, cache: Cache, staged, S: int) -> Cache:
    if "page_table" in cache:
        raise NotImplementedError(
            "prefill writes a dense cache; paged serving prefills a dense "
            "bucketed B=1 cache and scatters it with write_slot, or chunks "
            "the prompt through decode_step + commit_cache "
            "(engine.prefill_chunk_stage)"
        )
    segs = layout(cfg)
    new_segments = []
    for si, seg in enumerate(segs):
        new_unit = []
        for u, spec in enumerate(seg.unit):
            c = cache["segments"][si][u]
            st = staged[si][u]
            if spec.block is BlockKind.ATTENTION:
                S_c = c["k"].shape[2]
                k, v = st["k"], st["v"]               # (R, B, S, KV, hd)
                if S_c >= S:
                    newk = jax.lax.dynamic_update_slice_in_dim(c["k"], k.astype(c["k"].dtype), 0, axis=2)
                    newv = jax.lax.dynamic_update_slice_in_dim(c["v"], v.astype(c["v"].dtype), 0, axis=2)
                else:
                    # ring: keep last S_c tokens arranged by pos % S_c
                    last = S - 1
                    slots = jnp.arange(S_c)
                    src = last - ((last - slots) % S_c)   # position stored in slot
                    newk = jnp.take(k, src, axis=2).astype(c["k"].dtype)
                    newv = jnp.take(v, src, axis=2).astype(c["v"].dtype)
                new_unit.append({"k": newk, "v": newv})
            else:
                # staged mamba leaves carry a length-1 step axis after batch
                new_unit.append(
                    jax.tree.map(
                        lambda a, old: a[:, :, 0].astype(old.dtype), st, c
                    )
                )
        new_segments.append(new_unit)
    batch = cache["pos"].shape[0]
    return {"pos": jnp.full((batch,), S, jnp.int32), "segments": new_segments}


def decode_step(
    cfg: ModelConfig,
    params: dict,
    cache: Cache,
    tokens: jax.Array,                # (B, T) or (B, T, nc)
    *,
    gates: Optional[jax.Array] = None,
    tree_mask: Optional[jax.Array] = None,   # (T, T) or (B, T, T) ancestor-or-self
    q_pos: Optional[jax.Array] = None,       # (T,) or (B, T) absolute positions
    attn_override: Optional[dict] = None,    # efficient-attention DSIA
    seq_axes: Optional[tuple] = None,        # context-parallel cache partials
    attn_backend: Optional[str] = None,      # "pallas": kernel tree-verify pass
    quantize: Optional[str] = None,          # "int8": W8A8 MLP matmuls (DSIA)
    staged_kv: Optional[Any] = None,         # carried draft-KV buffers (carry)
    staged_pos: Optional[jax.Array] = None,  # (B, N_s) staged-row positions
    staged_mask: Optional[jax.Array] = None, # (B, T, N_s) staged visibility
) -> Tuple[jax.Array, Any]:
    """Stage-only decode of T tokens against a frozen cache.

    Returns (logits (B,T,[nc,]V), staged) — commit with ``commit_cache``.
    A 3-D tree mask carries one ancestor-closure per sequence (batched tree
    verification); paired with a (B, T) ``q_pos`` of per-node depths.
    ``quantize="int8"`` routes the dense-MLP matmuls through the Pallas
    W8A8 kernel (ActivationQuant DSIA drafting; TPU-compiled). ``params``
    then hold the MLP weights' int8 images (``int8_mlp_params``); off-TPU
    callers simulate with ``engine.fake_quant_int8`` params instead.

    Incremental mode (``draft_kv="carry"`` in the engine scans): pass
    ``staged_kv`` — a carried pytree with the same structure a previous
    ``decode_step`` returned as ``staged`` (per-layer (R, B, N_s, KV, hd)
    K/V blocks) — plus ``staged_pos``/``staged_mask``. The T new tokens then
    attend over [committed cache ++ carried staged rows ++ themselves],
    so an expansion step decodes only its appended tokens instead of
    re-decoding the whole padded block. The returned ``staged`` holds the
    NEW rows only; the caller scatters them into its carried buffers at the
    append indices (write cursor = the tree's ``count``). Attention-only
    stacks: SSM per-step states are cumulative and cannot be carried
    row-wise (the engine guards this).
    """
    h = _embed(cfg, params, {"tokens": tokens})
    B, T = tokens.shape[0], tokens.shape[1]
    if q_pos is None:
        q_pos = cache["pos"][:, None] + jnp.arange(T, dtype=jnp.int32)[None]
    elif q_pos.ndim == 1:
        q_pos = jnp.broadcast_to(q_pos[None], (B, T))
    h, staged, _ = _run_stack(
        cfg, params, h, mode="decode", cache=cache, gates=gates,
        q_pos=q_pos, tree_mask=tree_mask, attn_override=attn_override,
        seq_axes=seq_axes, attn_backend=attn_backend, quantize=quantize,
        staged_kv=staged_kv, staged_pos=staged_pos, staged_mask=staged_mask,
    )
    return _head(cfg, params, h), staged


def decode_commit_token(
    cfg: ModelConfig,
    params: dict,
    cache: Cache,
    token: jax.Array,                 # (B,) one token per sequence
    *,
    gates: Optional[jax.Array] = None,
    attn_override: Optional[dict] = None,
) -> Tuple[jax.Array, Cache]:
    """Scan-friendly single-token decode: decode one token per sequence and
    immediately commit its staged KV/state, advancing ``pos`` by one.

    Unlike ``decode_step`` this WRITES the cache. It exists for the draft
    side of chain speculation, where the k-step drafting loop runs as one
    jitted ``lax.scan`` with the cache as carry — every drafted token must be
    visible to the next draft step without a host round trip. Draft scratch
    caches are discarded after proposing, so the losslessness invariant
    (only verified tokens reach the *committed* cache) is untouched.

    Returns (logits (B, V), new_cache). Codebook (audio) models are not
    supported on this path — their tokens are (B, nc), not scalar.
    """
    logits, staged = decode_step(
        cfg, params, cache, token[:, None], gates=gates,
        attn_override=attn_override,
    )
    B = token.shape[0]
    path_idx = jnp.zeros((B, 1), jnp.int32)
    new_cache = commit_cache(
        cfg, cache, staged, path_idx, jnp.ones((B,), jnp.int32)
    )
    return logits[:, 0], new_cache


def write_slot(cfg: ModelConfig, cache: Cache, c1: Cache, slot) -> Cache:
    """Write a freshly prefilled B=1 cache into batch slot ``slot`` of the
    batched cache — one dynamic-update per leaf, jit-friendly (``slot`` may
    be traced, so one executable serves every slot). Jitted with the batched
    cache donated, admission updates the largest live buffer in place
    instead of round-tripping a full copy through the host.

    ``c1`` may be allocated at a padded BUCKET shorter than the batched
    cache's ``max_len`` (admission sizes it to the prompt, not the worst
    case): its rows land at the front of the slot, and rows past
    ``c1["pos"]`` are never read (kv_pos masking), so the leftover tail
    from the slot's previous occupant is as invisible as the zeros a
    full-length prefill cache used to write there. When the batched cache
    is PAGED, the same rows scatter through ``page_table[slot]`` instead —
    the pages must have been allocated (table row set) before the call.
    """
    segs = layout(cfg)
    paged = "page_table" in cache
    new_segments = []
    for si, seg in enumerate(segs):
        new_unit = []
        for u, spec in enumerate(seg.unit):
            dst = cache["segments"][si][u]
            src = c1["segments"][si][u]
            if spec.block is BlockKind.ATTENTION and paged:
                table = cache["page_table"]
                pool = dst["k_pages"]
                NP, P_sz = pool.shape[1], pool.shape[2]
                tbl_row = table[slot]                       # (n_pp,)
                rows = jnp.arange(src["k"].shape[2], dtype=jnp.int32)
                page = jnp.take(
                    tbl_row, jnp.clip(rows // P_sz, 0, tbl_row.shape[0] - 1)
                )
                # unallocated page -> OOB sentinel, dropped by the scatter;
                # offset by the logical page so (page, off) pairs stay
                # unique (duplicates under unique_indices=True are UB)
                page = jnp.where(page >= 0, page, NP + rows // P_sz)
                off = rows % P_sz
                ent = {}
                for name in ("k", "v"):
                    s = src[name][:, 0].astype(dst[name + "_pages"].dtype)
                    ent[name + "_pages"] = dst[name + "_pages"].at[
                        :, page, off
                    ].set(s, mode="drop", unique_indices=True)
                new_unit.append(ent)
            elif spec.block is BlockKind.ATTENTION:
                S_c = dst["k"].shape[2]
                S_src = src["k"].shape[2]
                if S_src == S_c:
                    new_unit.append(jax.tree.map(
                        lambda d, s: d.at[:, slot].set(s[:, 0].astype(d.dtype)),
                        dst, src,
                    ))
                elif S_src < S_c:
                    new_unit.append({
                        name: jax.lax.dynamic_update_slice(
                            dst[name],
                            src[name].astype(dst[name].dtype),
                            (0, slot, 0, 0, 0),
                        )
                        for name in ("k", "v")
                    })
                else:
                    raise NotImplementedError(
                        f"prefill cache seq {S_src} exceeds batched cache "
                        f"seq {S_c} (ring slots cannot take longer buckets)"
                    )
            else:
                new_unit.append(jax.tree.map(
                    lambda d, s: d.at[:, slot].set(s[:, 0].astype(d.dtype)),
                    dst, src,
                ))
        new_segments.append(new_unit)
    out = dict(cache)
    out["pos"] = cache["pos"].at[slot].set(c1["pos"][0])
    out["segments"] = new_segments
    return out


def commit_cache(
    cfg: ModelConfig,
    cache: Cache,
    staged,
    path_idx: jax.Array,              # (T,) or (B,T) indices into the staged T dim
    n_accept: jax.Array,              # scalar or (B,) int32 accepted count (<= T)
) -> Cache:
    """Write the accepted draft path into the cache and advance pos.

    Per-sequence ``path_idx``/``n_accept`` supports batched serving where
    different sequences accept different draft prefixes.
    """
    segs = layout(cfg)
    base = cache["pos"]                              # (B,)
    B = base.shape[0]
    if path_idx.ndim == 1:
        path_idx = jnp.broadcast_to(path_idx[None], (B, path_idx.shape[0]))
    T = path_idx.shape[1]
    n_acc = jnp.broadcast_to(jnp.asarray(n_accept, jnp.int32), (B,))
    step = jnp.arange(T, dtype=jnp.int32)
    live = step[None] < n_acc[:, None]               # (B, T)
    b_idx = jnp.arange(B)[:, None]
    new_segments = []
    for si, seg in enumerate(segs):
        new_unit = []
        for u, spec in enumerate(seg.unit):
            c = cache["segments"][si][u]
            st = staged[si][u]
            if spec.block is BlockKind.ATTENTION and "k_pages" in c:
                # paged commit: same gather of the accepted path, but the
                # destination row (pos + step) routes through the page
                # table — rejected rows AND rows whose page is unallocated
                # get the OOB sentinel page and are dropped in place
                NP, P_sz = c["k_pages"].shape[1], c["k_pages"].shape[2]
                table = cache["page_table"]                      # (B, n_pp)
                n_pp = table.shape[1]
                gidx = path_idx[None, :, :, None, None]          # (1,B,T,1,1)
                k = jnp.take_along_axis(
                    st["k"].astype(c["k_pages"].dtype), gidx, axis=2
                )
                v = jnp.take_along_axis(
                    st["v"].astype(c["v_pages"].dtype), gidx, axis=2
                )
                dest = base[:, None] + step[None]                # (B, T)
                pg_log = dest // P_sz
                page = jnp.take_along_axis(
                    table, jnp.clip(pg_log, 0, n_pp - 1), axis=1
                )
                ok = live & (pg_log < n_pp) & (page >= 0)
                # dropped rows need an OOB page that is UNIQUE per (b, t):
                # a shared sentinel would repeat (page, off) pairs across
                # slots, and duplicate indices under unique_indices=True
                # are undefined behavior (nondeterministic on CPU)
                oob = NP + b_idx * T + step[None]                # (B, T)
                page = jnp.where(ok, page, oob)
                off = dest % P_sz
                ck = c["k_pages"].at[:, page, off].set(
                    k, mode="drop", unique_indices=True
                )
                cv = c["v_pages"].at[:, page, off].set(
                    v, mode="drop", unique_indices=True
                )
                new_unit.append({"k_pages": ck, "v_pages": cv})
            elif spec.block is BlockKind.ATTENTION:
                S_c = c["k"].shape[2]
                gidx = path_idx[None, :, :, None, None]          # (1,B,T,1,1)
                # cast BEFORE the gather/scatter chain: the staged tensors
                # cross shards on their way to the cache owners — in bf16,
                # not f32 (halves the commit collective)
                k = jnp.take_along_axis(st["k"].astype(c["k"].dtype), gidx, axis=2)
                v = jnp.take_along_axis(st["v"].astype(c["v"].dtype), gidx, axis=2)
                dest = base[:, None] + step[None]                # (B, T)
                ring = S_c <= cfg.sliding_window and spec.attn is AttentionKind.SLIDING
                if ring:
                    dest = dest % S_c
                # copy-free in-place commit: rejected slots get an
                # OUT-OF-BOUNDS dest — jax scatter drops OOB updates
                # (mode='drop'), so no old-row gather, no trash row, and
                # the scatter can alias the donated cache in place. The
                # OOB dest is offset per step: repeated indices under
                # unique_indices=True are undefined behavior even when
                # every duplicate is dropped.
                dest = jnp.where(live, dest, S_c + step[None])
                ck = c["k"].at[:, b_idx, dest].set(
                    k, mode="drop", unique_indices=True
                )
                cv = c["v"].at[:, b_idx, dest].set(
                    v, mode="drop", unique_indices=True
                )
                new_unit.append({"k": ck, "v": cv})
            else:
                # staged mamba leaves: (R, B, T, ...) per-step states
                idx = jnp.clip(n_acc - 1, 0, T - 1)              # (B,)
                keep = (n_acc == 0)

                def commit_state(a, old):
                    idx_e = idx.reshape((1, B, 1) + (1,) * (a.ndim - 3))
                    new = jnp.take_along_axis(a, idx_e, axis=2)[:, :, 0]
                    keep_e = keep.reshape((1, B) + (1,) * (old.ndim - 2))
                    return jnp.where(keep_e, old, new.astype(old.dtype))

                new_unit.append(jax.tree.map(commit_state, st, c))
        new_segments.append(new_unit)
    out = dict(cache)                 # paged caches carry their page_table
    out["pos"] = base + n_acc
    out["segments"] = new_segments
    return out
