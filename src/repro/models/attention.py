"""GQA attention: blockwise (memory-efficient) train/prefill path and a
cache + staged-draft decode path with tree masks.

Pure jnp with online softmax over KV chunks — this is the portable reference
path used for CPU execution and for multi-pod dry-runs. The Pallas kernels in
``repro.kernels`` implement the same contracts for the TPU hot spots and are
validated against these functions.

Sharding note: scores are computed in EXPANDED-head form — K/V are repeated
from KV to H = KV*rep heads before the einsum, so the contraction is only
over head_dim (never sharded) and the score/output tensors are sharded on H.
With KV the major factor of H, a KV-head sharding propagates through the
repeat; with Q-head sharding (KV < mesh axis) the replicated K/V expand into
H-sharded scores. Sharding the head_dim contraction (the naive GQA layout)
costs a per-chunk all-reduce of the score tensor — measured at up to ~10 TB
per prefill step before this layout (see EXPERIMENTS.md §Perf).

Layouts:
  q/k/v activations: (B, S, H, head_dim) / (B, S, KV, head_dim)
  KV cache:          (B, S_cache, KV, head_dim)  — seq dim shardable ("data")

Mask kinds:
  causal     — kv_pos <= q_pos
  window     — causal and kv_pos > q_pos - window
  streaming  — causal and (kv_pos < sink or kv_pos > q_pos - window)  [StreamingLLM]
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _mask(
    q_pos: jax.Array,          # (..., Tq) int32
    kv_pos: jax.Array,         # (..., Tk) int32, -1 marks an invalid slot
    kind: str,
    window: int,
    sink: int,
) -> jax.Array:
    """Boolean (..., Tq, Tk) visibility mask."""
    q = q_pos[..., :, None]
    k = kv_pos[..., None, :]
    valid = (k >= 0) & (k <= q)
    if kind == "window":
        valid &= k > q - window
    elif kind == "streaming":
        valid &= (k < sink) | (k > q - window)
    elif kind != "causal":
        raise ValueError(f"unknown mask kind {kind!r}")
    return valid


def _expand_kv(k: jax.Array, rep: int) -> jax.Array:
    """(B, S, KV, hd) -> (B, S, H, hd) with KV the major factor of H."""
    if rep == 1:
        return k
    return jnp.repeat(k, rep, axis=2)


def _scores(q: jax.Array, k: jax.Array) -> jax.Array:
    """q (B,Tq,H,hd) x k (B,Tk,H,hd) -> (B,H,Tq,Tk), float32."""
    return jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)


def _out(p: jax.Array, v: jax.Array) -> jax.Array:
    """p (B,H,Tq,Tk) x v (B,Tk,H,hd) -> (B,Tq,H,hd), float32."""
    return jnp.einsum("bhqk,bkhd->bqhd", p, v, preferred_element_type=jnp.float32)


def blockwise_attention(
    q: jax.Array,              # (B, Tq, H, hd)
    k: jax.Array,              # (B, Tk, KV, hd)
    v: jax.Array,              # (B, Tk, KV, hd)
    q_pos: jax.Array,          # (Tq,) int32
    kv_pos: jax.Array,         # (Tk,) int32
    *,
    kind: str = "causal",
    window: int = 0,
    sink: int = 0,
    chunk_q: int = 512,
    chunk_kv: int = 1024,
) -> jax.Array:
    """Memory-efficient causal/window attention; returns (B, Tq, H, hd)."""
    B, Tq, H, hd = q.shape
    KV = k.shape[2]
    rep = H // KV
    scale = hd ** -0.5

    cq = min(chunk_q, Tq)
    ck = min(chunk_kv, k.shape[1])
    pq = (-Tq) % cq
    pk = (-k.shape[1]) % ck
    qp = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    qpos = jnp.pad(q_pos, (0, pq), constant_values=jnp.int32(2**30))
    kpos = jnp.pad(kv_pos, (0, pk), constant_values=jnp.int32(-1))
    nq = qp.shape[1] // cq
    nk = kp.shape[1] // ck

    Tkp = kp.shape[1]
    qp = (qp * scale).reshape(B, nq, cq, H, hd)
    qpos_b = qpos.reshape(nq, cq)

    # window-chunk skipping: a q block only touches KV in a fixed-size span
    # ending at its last position — O(S * window) FLOPs instead of O(S^2).
    # (causal full attention keeps the all-chunks scan + masks.)
    windowed = kind == "window" and 0 < window and window + 2 * ck < Tkp

    def scan_kv(qi, qpos_i, ks, vs, kpos_s):
        nkk = ks.shape[1] // ck

        def kv_step(carry, xs):
            m, l, acc = carry
            kj, vj, kpos_j = xs
            kj = _expand_kv(kj, rep)
            vj = _expand_kv(vj, rep)
            s = _scores(qi, kj)                      # (B,H,cq,ck)
            msk = _mask(qpos_i, kpos_j, kind, window, sink)
            s = jnp.where(msk[None, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)                # (B,H,cq)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr.transpose(0, 2, 1)[..., None] + _out(
                p.astype(qi.dtype), vj
            )
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, H, cq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, H, cq), jnp.float32)
        a0 = jnp.zeros((B, cq, H, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step,
            (m0, l0, a0),
            (
                jnp.moveaxis(ks.reshape(B, nkk, ck, KV, hd), 1, 0),
                jnp.moveaxis(vs.reshape(B, nkk, ck, KV, hd), 1, 0),
                kpos_s.reshape(nkk, ck),
            ),
        )
        l = jnp.maximum(l, 1e-30)
        return acc / l.transpose(0, 2, 1)[..., None]

    # re-pin after jnp.pad: the pad output's sharding is re-decided by GSPMD
    # and the downstream (seq-sharded) cache spec otherwise pulls S onto
    # 'model', making every kv-chunk slice of the scan an all-gather
    # (measured 805 MB/layer on musicgen prefill)
    from repro.models.shard_utils import constrain as _cst, data_axis as _dx
    kp = _cst(kp, _dx(), None, None, None)
    vp = _cst(vp, _dx(), None, None, None)
    qp = _cst(qp, _dx(), None, None, None, None)   # (B, nq, cq, H, hd)

    if windowed:
        span = ((window + cq + ck - 1) // ck + 1) * ck   # covers window + slack

        def q_block(args):
            qi, qpos_i = args
            # derive block end from the FIRST position (padded tail entries
            # carry sentinel positions)
            q_end = qpos_i[0] + cq - 1
            start = jnp.clip(q_end + 1 - span, 0, Tkp - span)
            ks = jax.lax.dynamic_slice(kp, (0, start, 0, 0), (B, span, KV, hd))
            vs = jax.lax.dynamic_slice(vp, (0, start, 0, 0), (B, span, KV, hd))
            kpos_s = jax.lax.dynamic_slice(kpos, (start,), (span,))
            return scan_kv(qi, qpos_i, ks, vs, kpos_s)
    else:
        def q_block(args):
            qi, qpos_i = args
            return scan_kv(qi, qpos_i, kp, vp, kpos)

    out = jax.lax.map(q_block, (jnp.moveaxis(qp, 1, 0), qpos_b))  # (nq,B,cq,H,hd)
    out = jnp.moveaxis(out, 0, 1).reshape(B, nq * cq, H, hd)[:, :Tq]
    return out.astype(q.dtype)


def _staged_pallas_partials(
    q: jax.Array,              # (B, T, H, hd) — ALREADY scaled
    k_new: jax.Array,          # (B, T, KV, hd)
    v_new: jax.Array,
    vis: jax.Array,            # (B, T, T) bool — tree & positional validity
    rep: int,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Intra-tree softmax partials via the Pallas tree-attention kernel.

    Same row layout as ``kernels.ops.verify_attention`` (row = r*T + t per
    (batch, kv-head) grid step, head_dim padded to the 128-lane tile);
    interpret mode off-TPU. Returns (acc (B,T,H,hd), m (B,H,T), l (B,H,T)).

    Under a mesh in context the partitioner cannot split a Mosaic kernel,
    so the call runs under ``shard_map``: batch over the data axes (when
    they divide B) and KV heads over ``model`` when it divides KV (the
    ``kv`` head policy); otherwise each model shard runs the whole batch
    shard.
    """
    from repro.kernels.tree_attention import tree_attention_partial
    from repro.models.shard_utils import DATA_AXES, _mesh_axes, resolve_spec

    B, T, H, hd = q.shape
    KV = k_new.shape[2]
    qr = q.reshape(B, T, KV, rep, hd).transpose(0, 2, 3, 1, 4).reshape(
        B, KV, rep * T, hd
    )
    kn = k_new.transpose(0, 2, 1, 3)
    vn = v_new.transpose(0, 2, 1, 3)
    pad = (-hd) % 128
    if pad:
        widths = ((0, 0), (0, 0), (0, 0), (0, pad))
        qr, kn, vn = (jnp.pad(a, widths) for a in (qr, kn, vn))
    kernel = functools.partial(
        tree_attention_partial,
        interpret=jax.default_backend() != "tpu", scale=1.0,
    )
    if _mesh_axes():
        from jax.sharding import PartitionSpec as P

        dp, kh, _, _ = resolve_spec(qr.shape, DATA_AXES, "model", None, None)
        heads = P(dp, kh, None, None)
        kernel = jax.shard_map(
            kernel, in_specs=(heads, heads, heads, P(dp, None, None)),
            out_specs=(heads, P(dp, kh, None), P(dp, kh, None)),
            check_vma=False,
        )
    acc, m, l = kernel(qr, kn, vn, vis)
    acc = acc[..., :hd].reshape(B, KV, rep, T, hd).transpose(0, 3, 1, 2, 4)
    return acc.reshape(B, T, H, hd), m.reshape(B, H, T), l.reshape(B, H, T)


def decode_attention(
    q: jax.Array,              # (B, T, H, hd) — T = 1 (AR) or draft bucket
    k_cache: jax.Array,        # (B, S_c, KV, hd)
    v_cache: jax.Array,        # (B, S_c, KV, hd)
    cache_pos: jax.Array,      # (B,) int32: committed tokens per sequence
    k_new: jax.Array,          # (B, T, KV, hd) staged draft keys (not committed)
    v_new: jax.Array,          # (B, T, KV, hd)
    q_pos: jax.Array,          # (B, T) absolute positions of the draft tokens
    *,
    tree_mask: Optional[jax.Array] = None,   # (T, T) or (B, T, T) bool mask
    kind: str = "causal",
    window: int = 0,
    sink: int = 0,
    ring: bool = False,        # cache is a ring buffer of size S_c (= window)
    chunk_kv: int = 4096,
    seq_axes: Optional[Tuple[str, ...]] = None,  # context-parallel partials
    backend: Optional[str] = None,   # "pallas": kernel staged pass (tree verify)
    k_staged: Optional[jax.Array] = None,    # (B, N_s, KV, hd) carried draft KV
    v_staged: Optional[jax.Array] = None,    # (B, N_s, KV, hd)
    staged_pos: Optional[jax.Array] = None,  # (B, N_s) absolute node positions
    staged_mask: Optional[jax.Array] = None, # (B, T, N_s) bool visibility
) -> jax.Array:
    """Attention of T staged tokens over [committed cache ++ staged draft].

    Returns (B, T, H, hd). The cache is read-only here — commit happens after
    verification (see models.model.commit_cache). Tree mask gives intra-draft
    visibility (ancestor-closure of the draft token tree); None means chain.
    A 2-D (T, T) mask is shared across the batch; a 3-D (B, T, T) mask gives
    every sequence its own tree (the batched ``tree_fused`` serving mode).
    ``backend="pallas"`` routes the dense intra-tree pass through
    ``kernels.tree_attention`` and merges its partials with the cache scan.

    ``k_staged``/``v_staged`` enable the incremental drafting path
    (``draft_kv="carry"`` in the engine scans): a fixed-size block of
    PREVIOUSLY staged draft KV that the T new queries attend over in
    addition to the committed cache and themselves. ``staged_mask`` carries
    the tree/causal visibility of each staged row to each query (stale rows
    masked off by the caller), ``staged_pos`` its absolute positions so the
    window/streaming mask kinds apply exactly as they do to the in-block
    pass. Like the cache, the staged block is read-only here — the caller
    scatters the RETURNED new rows into its carried buffers.

    ``seq_axes`` switches the cache pass from the sequential chunk-scan to
    flash-decoding split-KV: the seq dim reshapes to (n, S/n) with n = the
    product of the named mesh axes, and partial (m, l, acc) are computed
    DENSELY per slice in one einsum, then merged with a logsumexp combine.
    The slice dim is pinned to ``seq_axes`` (and q/partials pinned local)
    so each shard computes its slice in place and the combine is the only
    cross-shard communication — a (B,H,T)-stat + (B,T,H,hd) all-reduce
    instead of gathering the whole cache (the GSPMD context-parallel
    decode). Without the pins, GSPMD back-propagates the H sharding of the
    output projection through the chain and gathers the cache (~2 GiB/layer
    measured on internlm2 decode_32k).
    """
    B, T, H, hd = q.shape
    KV = k_cache.shape[2]
    rep = H // KV
    scale = hd ** -0.5
    S_c = k_cache.shape[1]
    q = q * scale

    cache_pos = jnp.broadcast_to(jnp.asarray(cache_pos, jnp.int32), (B,))
    if q_pos.ndim == 1:
        q_pos = jnp.broadcast_to(q_pos[None], (B, T))

    # positions of cache slots, per sequence: (B, S_c)
    slots = jnp.arange(S_c, dtype=jnp.int32)[None]
    if ring:
        last = cache_pos[:, None] - 1
        # most recent position stored in slot j (writes go to pos % S_c)
        p = last - ((last - slots) % S_c)
        kv_pos = jnp.where((p >= 0) & (p <= last), p, jnp.int32(-1))
    else:
        kv_pos = jnp.where(slots < cache_pos[:, None], slots, jnp.int32(-1))

    n_seq = 0
    if seq_axes:
        from repro.models.shard_utils import _mesh_axes, constrain, data_axis

        sizes = _mesh_axes()
        if all(a in sizes for a in seq_axes):
            n_seq = 1
            for a in seq_axes:
                n_seq *= sizes[a]

    if n_seq > 1:
        # --- flash-decoding split-KV: dense partials per seq slice
        dp = data_axis()
        if dp is not None:  # batch axes must not repeat the seq axes
            dp = tuple(a for a in ((dp,) if isinstance(dp, str) else dp)
                       if a not in seq_axes) or None
        # q replicated over the seq axes (moving q is a few MB; the pins on
        # s/acc_p below stop GSPMD from gathering the cache instead)
        q = constrain(q, dp, None, None, None)
        n = n_seq
        pk = (-S_c) % n
        kc = jnp.pad(k_cache, ((0, 0), (0, pk), (0, 0), (0, 0)))
        vc = jnp.pad(v_cache, ((0, 0), (0, pk), (0, 0), (0, 0)))
        kpos = jnp.pad(kv_pos, ((0, 0), (0, pk)), constant_values=jnp.int32(-1))
        Sl = kc.shape[1] // n
        kc = constrain(kc.reshape(B, n, Sl, KV, hd), dp, seq_axes, None, None, None)
        vc = constrain(vc.reshape(B, n, Sl, KV, hd), dp, seq_axes, None, None, None)
        kpos = kpos.reshape(B, n, Sl)
        # grouped GQA einsum — the rep expansion is NEVER materialized
        # (repeating the cache slice costs rep x its bytes in HBM traffic;
        # measured 59 GiB/dev -> see EXPERIMENTS.md §Perf internlm2 decode)
        q5 = q.reshape(B, T, KV, rep, hd)
        s = jnp.einsum(
            "btgrd,bnsgd->bngrts", q5, kc, preferred_element_type=jnp.float32
        )                                            # (B,n,KV,rep,T,Sl)
        s = constrain(s, dp, seq_axes, None, None, None, None)
        msk = _mask(q_pos[:, None], kpos, kind, window, sink)  # (B,n,T,Sl)
        s = jnp.where(msk[:, :, None, None], s, NEG_INF)
        m_p = jnp.max(s, axis=-1)                    # (B,n,KV,rep,T)
        p = jnp.exp(s - m_p[..., None])
        l_p = jnp.sum(p, axis=-1)
        acc_p = jnp.einsum(
            "bngrts,bnsgd->bntgrd", p.astype(q.dtype), vc,
            preferred_element_type=jnp.float32,
        )                                            # (B,n,T,KV,rep,hd)
        acc_p = constrain(acc_p, dp, seq_axes, None, None, None, None)
        # flatten (KV, rep) -> H for the shared combine below
        m_p = m_p.reshape(B, n, H, T)
        l_p = l_p.reshape(B, n, H, T)
        acc_p = acc_p.reshape(B, n, T, H, hd)
        # --- logsumexp combine across slices (the only cross-shard comms)
        # the acc payload crosses the ICI in bf16 (halves the all-reduce
        # bytes; stats stay f32; the final 1/l normalization is f32)
        m_c = jnp.max(m_p, axis=1)                   # (B,H,T)
        w = jnp.exp(m_p - m_c[:, None])              # (B,n,H,T)
        l_c = jnp.sum(l_p * w, axis=1)
        acc_w = (acc_p * w.transpose(0, 1, 3, 2)[..., None]).astype(q.dtype)
        acc_c = jnp.sum(acc_w, axis=1).astype(jnp.float32)
    else:
        # --- sequential chunk-scan over the committed cache
        ck = min(chunk_kv, S_c)
        pk = (-S_c) % ck
        kc = jnp.pad(k_cache, ((0, 0), (0, pk), (0, 0), (0, 0)))
        vc = jnp.pad(v_cache, ((0, 0), (0, pk), (0, 0), (0, 0)))
        kpos = jnp.pad(kv_pos, ((0, 0), (0, pk)), constant_values=jnp.int32(-1))
        nk = kc.shape[1] // ck
        kc = kc.reshape(B, nk, ck, KV, hd)
        vc = vc.reshape(B, nk, ck, KV, hd)
        kpos = kpos.reshape(B, nk, ck)

        def kv_step(carry, xs):
            m, l, acc = carry
            kj, vj, kpos_j = xs
            kj = _expand_kv(kj, rep)
            vj = _expand_kv(vj, rep)
            s = _scores(q, kj)                           # (B,H,T,ck)
            msk = _mask(q_pos, kpos_j, kind, window, sink)   # (B, T, ck)
            s = jnp.where(msk[:, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr.transpose(0, 2, 1)[..., None] + _out(
                p.astype(q.dtype), vj
            )
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, H, T), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, H, T), jnp.float32)
        a0 = jnp.zeros((B, T, H, hd), jnp.float32)
        (m_c, l_c, acc_c), _ = jax.lax.scan(
            kv_step,
            (m0, l0, a0),
            (jnp.moveaxis(kc, 1, 0), jnp.moveaxis(vc, 1, 0), jnp.moveaxis(kpos, 1, 0)),
        )

    # --- carried staged-KV pass (incremental drafting): merge the carried
    # draft rows into the cache partials before the in-block pass, so the
    # final merge below is untouched whichever mode runs
    if k_staged is not None:
        if staged_pos is None or staged_mask is None:
            raise ValueError("k_staged requires staged_pos and staged_mask")
        s_s = _scores(q, _expand_kv(k_staged, rep))          # (B,H,T,N_s)
        vis_s = _mask(q_pos, staged_pos, kind, window, sink) & staged_mask
        s_s = jnp.where(vis_s[:, None], s_s, NEG_INF)
        m_s = jnp.max(s_s, axis=-1)
        m_cs = jnp.maximum(m_c, m_s)
        p_s = jnp.exp(s_s - m_cs[..., None])
        corr_s = jnp.exp(m_c - m_cs)
        l_c = l_c * corr_s + jnp.sum(p_s, axis=-1)
        acc_c = acc_c * corr_s.transpose(0, 2, 1)[..., None] + _out(
            p_s.astype(q.dtype), _expand_kv(v_staged, rep)
        )
        m_c = m_cs

    # --- dense pass over the staged draft tokens
    vis = _mask(q_pos, q_pos, kind, window, sink)    # (B, T, T) positional validity
    if tree_mask is not None:
        vis = vis & (tree_mask if tree_mask.ndim == 3 else tree_mask[None])

    if backend == "pallas":
        acc_d, m_d, l_d = _staged_pallas_partials(q, k_new, v_new, vis, rep)
        m_tot = jnp.maximum(m_c, m_d)
        corr_c = jnp.exp(m_c - m_tot)
        corr_d = jnp.exp(m_d - m_tot)
        l_tot = l_c * corr_c + l_d * corr_d
        acc = (
            acc_c * corr_c.transpose(0, 2, 1)[..., None]
            + acc_d * corr_d.transpose(0, 2, 1)[..., None]
        )
    else:
        s_d = _scores(q, _expand_kv(k_new, rep))     # (B,H,T,T)
        s_d = jnp.where(vis[:, None], s_d, NEG_INF)
        # --- merge softmax accumulators
        m_d = jnp.max(s_d, axis=-1)
        m_tot = jnp.maximum(m_c, m_d)
        p_d = jnp.exp(s_d - m_tot[..., None])
        corr_c = jnp.exp(m_c - m_tot)
        l_tot = l_c * corr_c + jnp.sum(p_d, axis=-1)
        acc = acc_c * corr_c.transpose(0, 2, 1)[..., None] + _out(
            p_d.astype(q.dtype), _expand_kv(v_new, rep)
        )
    l_tot = jnp.maximum(l_tot, 1e-30)
    out = acc / l_tot.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)
