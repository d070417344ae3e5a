"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import quantized_matmul, verify_attention
from repro.kernels import ref as R
from repro.kernels.int8_matmul import (
    TILE_BYTES, VMEM_BUDGET, VMEM_LIMIT, _vmem_bytes, choose_blocks, int8_matmul,
    quantize_rows, quantize_weight,
)


def _mk(B, T, H, KV, hd, S, dtype, seed=0, pos=None, tree=True):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    q = jax.random.normal(ks[0], (B, T, H, hd), dtype)
    kc = jax.random.normal(ks[1], (B, S, KV, hd), dtype)
    vc = jax.random.normal(ks[2], (B, S, KV, hd), dtype)
    kn = jax.random.normal(ks[3], (B, T, KV, hd), dtype)
    vn = jax.random.normal(ks[4], (B, T, KV, hd), dtype)
    pos = S - 5 if pos is None else pos
    kv_pos = jnp.broadcast_to(
        jnp.where(jnp.arange(S)[None] < pos, jnp.arange(S)[None], -1).astype(jnp.int32),
        (B, S),
    )
    q_pos = (pos + jnp.arange(T))[None].repeat(B, 0).astype(jnp.int32)
    tm = np.tril(np.ones((T, T), bool))
    if tree and T >= 4:
        tm[3, 2] = False               # a branch
    tmask = jnp.broadcast_to(jnp.asarray(tm), (B, T, T))
    return q, kc, vc, kv_pos, q_pos, kn, vn, tmask


def _oracle(q, kc, vc, kv_pos, q_pos, kn, vn, tmask, **kw):
    B, T, H, hd = q.shape
    KV = kc.shape[2]
    rep = H // KV
    qr = q.reshape(B, T, KV, rep, hd).transpose(0, 2, 3, 1, 4).reshape(B, KV, rep * T, hd)
    ref = R.ref_verify_attention(
        qr, kc.transpose(0, 2, 1, 3), vc.transpose(0, 2, 1, 3),
        kv_pos, jnp.tile(q_pos, (1, rep)),
        kn.transpose(0, 2, 1, 3), vn.transpose(0, 2, 1, 3), tmask, **kw,
    )
    return ref.reshape(B, KV, rep, T, hd).transpose(0, 3, 1, 2, 4).reshape(B, T, H, hd)


@pytest.mark.parametrize(
    "B,T,H,KV,hd,S",
    [
        (1, 4, 2, 1, 32, 64),      # MQA
        (2, 8, 4, 2, 64, 128),     # GQA
        (1, 16, 8, 8, 80, 100),    # MHA, non-128 hd, ragged S
        (2, 8, 4, 4, 128, 256),
    ],
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_verify_attention_matches_oracle(B, T, H, KV, hd, S, dtype):
    args = _mk(B, T, H, KV, hd, S, dtype)
    out = verify_attention(*args, interpret=True)
    ref = _oracle(*[a.astype(jnp.float32) if a.dtype in (jnp.bfloat16,) else a for a in args])
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref), atol=tol, rtol=tol)


@pytest.mark.parametrize("kind,window,sink", [("window", 16, 0), ("streaming", 8, 2)])
def test_verify_attention_masked_kinds(kind, window, sink):
    args = _mk(1, 4, 4, 2, 64, 96, jnp.float32, seed=3)
    out = verify_attention(*args, kind=kind, window=window, sink=sink, interpret=True)
    ref = _oracle(*args, kind=kind, window=window, sink=sink)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_verify_attention_empty_cache():
    """pos=0 (nothing committed): only the tree part contributes."""
    args = _mk(1, 4, 2, 2, 32, 64, jnp.float32, pos=0)
    out = verify_attention(*args, interpret=True)
    ref = _oracle(*args)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# invalid-position masking property tests (satellite of the paged cache):
# the page gather relies ENTIRELY on the kv_pos = -1 contract to hide
# unallocated pages and partially-filled tails — these pin that contract on
# flash_decode_partial itself against the dense oracle.
# ---------------------------------------------------------------------------
from repro.kernels.flash_decode import (       # noqa: E402
    flash_decode_paged_partial, flash_decode_partial,
)


def _norm(acc, m, l):
    """Normalize flash partials to a full softmax (no staged half)."""
    return acc / jnp.maximum(l[..., None], 1e-30)


def _dense_oracle(q, k, v, kv_pos, q_pos, *, kind="causal", window=0, sink=0):
    """Full-softmax reference over the cache only (f32)."""
    s = jnp.einsum("bgrh,bgsh->bgrs", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * (q.shape[-1] ** -0.5)
    qp = q_pos[:, None, :, None]
    kp = kv_pos[:, None, None, :]
    valid = (kp >= 0) & (kp <= qp)
    if kind == "window":
        valid &= kp > qp - window
    elif kind == "streaming":
        valid &= (kp < sink) | (kp > qp - window)
    s = jnp.where(valid, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bgrs,bgsh->bgrh", p, v.astype(jnp.float32))


def _mk_partial(B, KV, R_, hd, S, seed, pos):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, KV, R_, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, KV, S, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, KV, S, hd), jnp.float32)
    kv_pos = jnp.where(
        jnp.arange(S)[None] < np.asarray(pos)[:, None],
        jnp.arange(S)[None], -1,
    ).astype(jnp.int32)
    q_pos = (np.asarray(pos)[:, None]
             + np.arange(R_)[None]).astype(np.int32)
    return q, k, v, kv_pos, jnp.asarray(q_pos)


@pytest.mark.parametrize("pos", [[0, 1], [5, 64], [37, 13]])
def test_flash_decode_invalid_rows_inert(pos):
    """Property: kv_pos=-1 slots NEVER contribute — poisoning their K/V
    with huge values must not change any query row that has at least one
    valid slot (bitwise: the poisoned lanes hit -inf before the softmax
    either way). A row with ZERO valid slots keeps garbage in its raw
    partials BY DESIGN: its ``m`` comes back as the -inf sentinel, which
    zeroes the whole cache half in the downstream logsumexp merge (the
    staged half always sees its own diagonal) — the exact contract the
    paged gather relies on for unallocated pages."""
    B, KV, R_, hd, S = 2, 2, 4, 64, 64
    q, k, v, kv_pos, q_pos = _mk_partial(B, KV, R_, hd, S, 7, pos)
    acc0, m0, l0 = flash_decode_partial(q, k, v, kv_pos, q_pos, block_s=32,
                                       interpret=True)
    bad = jnp.where((kv_pos < 0)[:, None, :, None], 1e4, 0.0)
    acc1, m1, l1 = flash_decode_partial(
        q, k + bad, v + bad, kv_pos, q_pos, block_s=32, interpret=True)
    has_valid = (jnp.asarray(pos) > 0)[:, None, None]   # any committed slot
    assert bool(jnp.all(jnp.where(has_valid, m0 == m1, True)))
    assert bool(jnp.all(jnp.where(has_valid, l0 == l1, True)))
    assert bool(jnp.all(jnp.where(has_valid[..., None], acc0 == acc1, True)))
    # all-invalid rows: the -inf sentinel that guarantees zero merge weight
    assert bool(jnp.all(jnp.where(~has_valid, m1 <= -1e30, True)))
    base = _norm(acc0, m0, l0)
    ref = _dense_oracle(q, k, v, kv_pos, q_pos)
    ok = np.asarray(jnp.broadcast_to(has_valid[..., None], ref.shape))
    np.testing.assert_allclose(np.asarray(base)[ok], np.asarray(ref)[ok],
                               atol=2e-5, rtol=2e-5)


def test_flash_decode_ring_wraparound():
    """Ring-buffer semantics: kv_pos carries ABSOLUTE positions that wrap
    modulo the window, so a scrambled (rolled) storage order with matching
    kv_pos must give the same output as the sorted order."""
    B, KV, R_, hd, S = 1, 2, 2, 64, 64
    window = S
    pos0 = 90                                   # wrapped: slot i holds
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(ks[0], (B, KV, R_, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, KV, S, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, KV, S, hd), jnp.float32)
    # ring layout: slot i holds absolute position (pos0 - window) + ...
    abs_pos = (pos0 - window + (jnp.arange(S) - pos0 % S) % S + S) % (10 * S)
    abs_pos = jnp.where(abs_pos < pos0, abs_pos, -1).astype(jnp.int32)[None]
    q_pos = jnp.asarray([[pos0, pos0 + 1]], jnp.int32)
    out_ring = _norm(*flash_decode_partial(
        q, k, v, abs_pos, q_pos, kind="window", window=window, block_s=32,
        interpret=True))
    # sorted layout: same (position, K, V) association, rolled into order
    order = jnp.argsort(jnp.where(abs_pos[0] < 0, 10**6, abs_pos[0]))
    out_sorted = _norm(*flash_decode_partial(
        q, jnp.take(k, order, 2), jnp.take(v, order, 2),
        jnp.take(abs_pos, order, 1), q_pos,
        kind="window", window=window, block_s=32, interpret=True))
    np.testing.assert_allclose(np.asarray(out_ring), np.asarray(out_sorted),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("kind,window,sink",
                         [("causal", 0, 0), ("window", 24, 0),
                          ("streaming", 16, 4)])
def test_flash_decode_paged_matches_dense(kind, window, sink):
    """The paged kernel (scalar-prefetched page table in the index_maps)
    is BITWISE the dense kernel on the gathered view — including a
    scrambled table, an unallocated (-1) tail and a partial tail page."""
    B, KV, R_, hd, P, n_pp = 2, 2, 4, 64, 16, 4
    S = n_pp * P
    NP = B * n_pp + 2
    rng = np.random.default_rng(3)
    perm = rng.permutation(NP)
    tbl = np.full((B, n_pp), -1, np.int32)
    tbl[0] = perm[:n_pp]
    tbl[1, :3] = perm[n_pp:n_pp + 3]            # slot 1: unallocated tail
    pos = [S - 7, 2 * P + 5]                    # partial tail pages
    q, _, _, kv_pos, q_pos = _mk_partial(B, KV, R_, hd, S, 5, pos)
    ks = jax.random.split(jax.random.PRNGKey(11), 2)
    pool_k = jax.random.normal(ks[0], (NP, KV, P, hd), jnp.float32)
    pool_v = jax.random.normal(ks[1], (NP, KV, P, hd), jnp.float32)
    k_dense = R.ref_paged_gather(pool_k, jnp.asarray(tbl))
    v_dense = R.ref_paged_gather(pool_v, jnp.asarray(tbl))
    ap, mp, lp = flash_decode_paged_partial(
        q, pool_k, pool_v, jnp.asarray(tbl), kv_pos, q_pos,
        kind=kind, window=window, sink=sink, interpret=True)
    ad, md, ld = flash_decode_partial(
        q, k_dense, v_dense, kv_pos, q_pos,
        kind=kind, window=window, sink=sink, block_s=P, interpret=True)
    assert bool(jnp.all(ap == ad) and jnp.all(mp == md) and jnp.all(lp == ld))


@pytest.mark.parametrize("M,K,N,dtype", [
    (8, 16, 8, jnp.float32), (100, 200, 300, jnp.float32),
    (128, 128, 128, jnp.float32), (1, 512, 64, jnp.float32),
    # M > 128 at starcoder2-3b's 1:4 MLP widths scaled down, in the
    # served dtype (the epilogue writes bf16)
    (384, 768, 3072, jnp.bfloat16),
])
def test_int8_matmul_matches_oracle(M, K, N, dtype):
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    x = jax.random.normal(k1, (M, K), dtype)
    w = jax.random.normal(k2, (K, N), dtype)
    image = quantize_weight(w)
    out = quantized_matmul(x, image, interpret=True)
    assert out.dtype == dtype
    xq, xs = quantize_rows(x)
    ref = R.ref_int8_matmul(xq, image.q, xs, image.scale).astype(dtype)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref, np.float32),
                               atol=1e-4, rtol=1e-4)
    # and the quantization error vs f32 is small
    exact = x.astype(jnp.float32) @ w.astype(jnp.float32)
    rel = float(jnp.mean(jnp.abs(out - exact)) / jnp.mean(jnp.abs(exact)))
    assert rel < 0.05


def test_int8_matmul_blocks_tile_the_call():
    """A grid of several (M, N) blocks writes the same result as the
    chosen blocks, which here cover the call in one."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(2))
    xq, xs = quantize_rows(jax.random.normal(k1, (256, 384)))
    image = quantize_weight(jax.random.normal(k2, (384, 512)))
    args = (xq, image.q, xs, image.scale)
    assert choose_blocks(256, 384, 512, 4) == (256, 512)
    whole = int8_matmul(*args, interpret=True)
    tiled = int8_matmul(*args, block_m=128, block_n=128, interpret=True)
    assert np.array_equal(np.asarray(whole), np.asarray(tiled))
    np.testing.assert_allclose(np.asarray(whole), np.asarray(R.ref_int8_matmul(*args)),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("K,N", [
    (3072, 12288), (12288, 3072),      # starcoder2-3b w_up, w_down
    (6144, 16384), (16384, 6144),      # internlm2-20b w_up/w_gate, w_down
])
@pytest.mark.parametrize("M", [128, 256, 512, 1024])
def test_int8_blocks_divide_and_fit(M, K, N):
    """The tile chooser at served widths: blocks divide the padded shapes,
    one M block holds a draft step's rows (each weight byte read once a
    call), weight tiles of a few MiB, everything within the VMEM budget."""
    bm, bn = choose_blocks(M, K, N, 2)
    assert M % bm == 0 and N % bn == 0 and bn % 128 == 0
    assert bm == min(M, 512)
    assert 2 << 20 <= K * bn <= TILE_BYTES
    assert _vmem_bytes(bm, bn, K, 2) <= VMEM_BUDGET < VMEM_LIMIT
