"""Pallas TPU kernel: W8A8 quantized matmul (ActivationQuant DSIA).

QSpec-style quantized drafting: activations are quantized per row on each
call; weights arrive already quantized per column (``Int8Weight``, made
once by ``quantize_weight``). The MXU runs the int8 x int8 -> int32 dot
over the whole K, so the sum is exact, and the epilogue rescales once.

Blocks follow the call's shapes (``choose_blocks``): one M block holds
every row of a draft step, so each weight byte is read once per call. The
weight stays in HBM and the kernel streams it through VMEM in (K, bn)
column tiles of a few MiB, so its own time covers reading the weight.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import NamedSharding, PartitionSpec as P

LANE = 128
MAX_BLOCK_M = 512
TILE_BYTES = 4 << 20     # largest weight tile (int8 bytes) a grid step streams
VMEM_BUDGET = 32 << 20   # what the chosen blocks may hold, double-buffered
VMEM_LIMIT = 48 << 20    # scoped VMEM asked of the compiler (v5e has 128 MiB)


class Int8Weight(NamedTuple):
    """A weight's int8 image: ``q`` (..., K, N) int8 and ``scale``
    (..., 1, N) float32, one symmetric scale per column over the whole K
    (per layer, for a stacked (R, K, N) weight)."""
    q: jax.Array
    scale: jax.Array

    def dequantize(self, dtype) -> jax.Array:
        return (self.q.astype(jnp.float32) * self.scale).astype(dtype)


def _kernel(x_ref, w_hbm, xs_ref, ws_hbm, o_hbm, w_buf, ws_buf, o_buf, sems,
            *, bm: int, bn: int):
    """One block of ``bm`` rows (in VMEM) against the whole weight, which
    stays in HBM: (K, bn) column tiles stream through two VMEM buffers, the
    next tile's copy in flight while the MXU works on this one, and each
    tile's result is copied back to HBM from two more."""
    nj = w_hbm.shape[1] // bn
    rows = pl.ds(pl.program_id(0) * bm, bm)

    def fetch(j):
        cols, slot = pl.ds(pl.multiple_of(j * bn, bn), bn), j % 2
        return (pltpu.make_async_copy(w_hbm.at[:, cols], w_buf.at[slot],
                                      sems.at[0, slot]),
                pltpu.make_async_copy(ws_hbm.at[:, cols], ws_buf.at[slot],
                                      sems.at[1, slot]))

    def store(j):
        cols, slot = pl.ds(pl.multiple_of(j * bn, bn), bn), j % 2
        return pltpu.make_async_copy(o_buf.at[slot], o_hbm.at[rows, cols],
                                     sems.at[2, slot])

    for c in fetch(0):
        c.start()

    @pl.loop(0, nj)
    def _(j):
        @pl.when(j + 1 < nj)
        def _():
            for c in fetch(j + 1):
                c.start()

        for c in fetch(j):
            c.wait()

        @pl.when(j >= 2)
        def _():
            store(j - 2).wait()                        # its buffer is free

        slot = j % 2
        acc = jax.lax.dot_general(
            x_ref[...], w_buf[slot], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )                                              # (bm, bn) exact
        o_buf[slot] = (
            acc.astype(jnp.float32) * xs_ref[...] * ws_buf[slot]
        ).astype(o_buf.dtype)
        store(j).start()

    for j in range(max(nj - 2, 0), nj):
        store(j).wait()


def quantize_rows(
    x: jax.Array, k_axis: str | None = None
) -> Tuple[jax.Array, jax.Array]:
    """Per-row symmetric int8: returns (x_int8 (M,K), scale (M,1) f32).

    ``k_axis`` names the mesh axis K is split over (inside ``shard_map``):
    the row maximum is then taken across it, so every shard quantizes on
    the unsplit row's grid."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    if k_axis is not None:
        amax = jax.lax.pmax(amax, k_axis)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127).astype(jnp.int8)
    return q, scale


def quantize_cols(w: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Per-column symmetric int8: returns (w_int8 (K,N), scale (1,N) f32)."""
    amax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=0, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(w.astype(jnp.float32) / scale), -127, 127).astype(jnp.int8)
    return q, scale


@jax.jit
def _quantize_stacked(w: jax.Array) -> Int8Weight:
    # one leading index at a time: a stacked (R, K, N) weight needs a
    # float32 copy of one layer, not of all R
    K, N = w.shape[-2:]
    q, s = jax.lax.map(quantize_cols, w.reshape(-1, K, N))
    return Int8Weight(q.reshape(w.shape), s.reshape(w.shape[:-2] + (1, N)))


def quantize_weight(w: jax.Array) -> Int8Weight:
    """The int8 image of ``w`` (..., K, N): ``quantize_cols`` over the
    whole K for each leading index. On a mesh the image is placed like
    ``w``: ``q`` as ``w``, the scales along ``w``'s N axis only."""
    image = _quantize_stacked(w)
    s = getattr(w, "sharding", None)
    if not isinstance(s, NamedSharding):
        return image
    spec = tuple(s.spec) + (None,) * (w.ndim - len(s.spec))
    scale = NamedSharding(s.mesh, P(*spec[:-2], None, spec[-1]))
    return jax.device_put(image, Int8Weight(s, scale))


def _vmem_bytes(bm: int, bn: int, K: int, out_bytes: int) -> int:
    """VMEM the kernel holds: every block double-buffered (the scale blocks
    padded to whole (8, 128) tiles) plus the dot's int32 result and its
    float32 rescale."""
    blocks = bm * K + K * bn + bm * LANE * 4 + 8 * bn * 4 + bm * bn * out_bytes
    return 2 * blocks + bm * bn * 8


def choose_blocks(M: int, K: int, N: int, out_bytes: int) -> Tuple[int, int]:
    """(bm, bn) for an (M, K) x (K, N) call, each dim a multiple of 128.

    K is never split. bm is the largest divisor of M up to 512 (all of M
    in a draft step), bn the widest column block whose (K, bn) weight tile
    stays within ``TILE_BYTES``; bm, then bn, shrink until the blocks fit
    ``VMEM_BUDGET``."""
    def divisors(n, cap):
        return [d for d in range(n, 0, -LANE) if n % d == 0 and d <= cap]

    for bm in divisors(M, MAX_BLOCK_M):
        for bn in divisors(N, max(TILE_BYTES // K, LANE)):
            if _vmem_bytes(bm, bn, K, out_bytes) <= VMEM_BUDGET:
                return bm, bn
    raise ValueError(f"no int8_matmul blocks fit VMEM for K={K}")


@functools.partial(
    jax.jit, static_argnames=("out_dtype", "block_m", "block_n", "interpret"))
def int8_matmul(
    x_q: jax.Array,      # (M, K) int8
    w_q: jax.Array,      # (K, N) int8
    x_scale: jax.Array,  # (M, 1) f32
    w_scale: jax.Array,  # (1, N) f32
    *,
    out_dtype=jnp.float32,
    block_m: int | None = None,
    block_n: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """(M, N) ``out_dtype``; M, K, N multiples of 128 (ops.py pads).
    Blocks default to ``choose_blocks``."""
    M, K = x_q.shape
    N = w_q.shape[1]
    assert M % LANE == 0 and N % LANE == 0 and K % LANE == 0, "pad in ops.py"
    bm, bn = choose_blocks(M, K, N, jnp.dtype(out_dtype).itemsize)
    bm, bn = block_m or bm, block_n or bn
    assert M % bm == 0 and N % bn == 0
    if not interpret:
        # the weight and its scales stay in HBM: the kernel streams them
        # itself, so no other op stages them in VMEM ahead of it (the
        # interpreter has no memory spaces)
        w_q, w_scale = (pltpu.with_memory_space_constraint(a, pltpu.HBM)
                        for a in (w_q, w_scale))
    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    return pl.pallas_call(
        functools.partial(_kernel, bm=bm, bn=bn),
        grid=(M // bm,),
        in_specs=[
            pl.BlockSpec((bm, K), lambda i: (i, 0)),
            hbm,
            pl.BlockSpec((bm, 1), lambda i: (i, 0)),
            hbm,
        ],
        out_specs=hbm,
        out_shape=pltpu.HBM((M, N), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((2, K, bn), jnp.int8),
            pltpu.VMEM((2, 1, bn), jnp.float32),
            pltpu.VMEM((2, bm, bn), out_dtype),
            pltpu.SemaphoreType.DMA((3, 2)),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT,
        ),
        interpret=interpret,
        name="int8_matmul",
    )(x_q, w_q, x_scale, w_scale)
