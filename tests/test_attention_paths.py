"""Attention execution paths agree: chunk-scan vs split-KV decode, windowed
chunk-skipping vs dense reference, ring-buffer caches."""
import subprocess
import sys
import os
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis", reason="needs hypothesis — pip install -r requirements-dev.txt")
from hypothesis import given, settings, strategies as st

from repro.models.attention import blockwise_attention, decode_attention


def dense_ref(q, k, v, q_pos, kv_pos, kind="causal", window=0, sink=0):
    B, S, H, hd = q.shape
    rep = H // k.shape[2]
    kx = jnp.repeat(k, rep, 2)
    vx = jnp.repeat(v, rep, 2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q * hd ** -0.5, kx)
    qq = q_pos[:, None]
    kk = kv_pos[None, :]
    m = (kk >= 0) & (kk <= qq)
    if kind == "window":
        m &= kk > qq - window
    elif kind == "streaming":
        m &= (kk < sink) | (kk > qq - window)
    s = jnp.where(m[None, None], s, -1e30)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), vx)


@pytest.mark.parametrize("kind,window", [("causal", 0), ("window", 48), ("window", 130)])
@pytest.mark.parametrize("chunks", [(32, 32), (64, 128)])
def test_blockwise_matches_dense(kind, window, chunks):
    cq, ck = chunks
    B, S, H, KV, hd = 2, 300, 4, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, KV, hd))
    v = jax.random.normal(ks[2], (B, S, KV, hd))
    pos = jnp.arange(S, dtype=jnp.int32)
    out = blockwise_attention(q, k, v, pos, pos, kind=kind, window=window,
                              chunk_q=cq, chunk_kv=ck)
    ref = dense_ref(q, k, v, pos, pos, kind=kind, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@given(
    pos=st.integers(1, 60),
    T=st.sampled_from([1, 4, 8]),
    window=st.sampled_from([0, 16]),
)
@settings(max_examples=12, deadline=None)
def test_decode_matches_dense_ref(pos, T, window):
    B, H, KV, hd, S = 2, 4, 2, 16, 64
    ks = jax.random.split(jax.random.PRNGKey(pos), 5)
    q = jax.random.normal(ks[0], (B, T, H, hd))
    kc = jax.random.normal(ks[1], (B, S, KV, hd))
    vc = jax.random.normal(ks[2], (B, S, KV, hd))
    kn = jax.random.normal(ks[3], (B, T, KV, hd))
    vn = jax.random.normal(ks[4], (B, T, KV, hd))
    kind = "window" if window else "causal"
    cp = jnp.full((B,), pos, jnp.int32)
    qpos = cp[:, None] + jnp.arange(T)[None]
    out = decode_attention(q, kc, vc, cp, kn, vn, qpos, kind=kind, window=window)
    # dense: concat cache (masked by pos) and staged
    kv_pos = jnp.where(jnp.arange(S) < pos, jnp.arange(S), -1)
    kall = jnp.concatenate([kc, kn], 1)
    vall = jnp.concatenate([vc, vn], 1)
    pall = jnp.concatenate([kv_pos, qpos[0]])
    ref = dense_ref(q, kall, vall, qpos[0], pall, kind=kind, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5, rtol=3e-5)


SPLIT_KV_SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.models.attention import decode_attention
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 4), ("data", "model"))
    jax.sharding.set_mesh(mesh)
    B, T, H, KV, hd, S = 4, 8, 8, 2, 32, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    q = jax.random.normal(ks[0], (B, T, H, hd))
    kc = jax.random.normal(ks[1], (B, S, KV, hd))
    vc = jax.random.normal(ks[2], (B, S, KV, hd))
    kn = jax.random.normal(ks[3], (B, T, KV, hd))
    vn = jax.random.normal(ks[4], (B, T, KV, hd))
    pos = jnp.full((B,), 50, jnp.int32)
    qpos = pos[:, None] + jnp.arange(T)[None]
    tm = jnp.asarray(np.tril(np.ones((T, T), bool)))
    for axes in [("model",), ("data", "model")]:
        a = jax.jit(lambda *x: decode_attention(*x, tree_mask=tm, seq_axes=axes))(
            q, kc, vc, pos, kn, vn, qpos)
        b = jax.jit(lambda *x: decode_attention(*x, tree_mask=tm))(
            q, kc, vc, pos, kn, vn, qpos)
        d = float(jnp.max(jnp.abs(a - b)))
        assert d < 1e-5, (axes, d)
    print("OK")
    """
)


@pytest.mark.slow
def test_split_kv_matches_scan_on_mesh():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", SPLIT_KV_SCRIPT],
                         capture_output=True, text=True, env=env, timeout=540)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout
