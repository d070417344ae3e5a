"""Set-up's warm-up: run every program the cell's window can call once,
on the cell's own shapes and no others.

1. Admission: one request of each distinct prompt length of the mix goes
   through the loop (a short budget each), so every prefill shape, the
   slot write and the round programs compile or load. The requests are
   made from a fixed seed, so every run warms the same work.
2. Variants the window may pick that step 1 cannot force. In
   ``cascade_fused`` rounds, DyTC picks any tree expansion count from 1 to
   ``tree_expansions`` (one drafting program each), and a round where no
   slot is routed through the rescorer verifies with the plain tree
   verify. Each is run once on the server's own example arguments
   (``round_executables``); the tree verify gets a copy of the cache to
   consume, since it donates its cache argument. ``round_executables``
   hands out only the deepest drafting program and the rescore-verify, so
   the other variants are reached through the server's own accessors
   (``_casc_draft_fn``, ``_tree_verify``): without them they would compile
   inside the window the first time DyTC picks them.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from harness import system, traffic

WARM_SEED = 7
WARM_TOKENS = 24


def admission(srv, sched, loop, mix: dict, vocab: int) -> int:
    rng = np.random.default_rng(WARM_SEED)
    lengths = traffic.warm_lengths(mix)
    for n in lengths:
        prompt = traffic.make_prompt(rng, n, vocab)
        sched.submit(system.request(prompt, WARM_TOKENS))
    steps = 0
    while sched.busy:
        loop.step_once()
        steps += 1
    srv.flush()
    return steps


def _host(x):
    """A round argument as the live call makes it, which sets the sharding
    it is traced with: arrays come from host arrays (no annotation),
    scalars from Python numbers (the mesh's annotation)."""
    a = np.asarray(x)
    if a.ndim == 0:
        return jnp.asarray(a.item(), a.dtype)
    return jnp.asarray(a)


def variants(srv) -> int:
    if srv.mode != "cascade_fused":
        return 0
    ex = srv.round_executables()
    _, args = ex["cascade_draft"]
    args = args[:2] + tuple(_host(a) for a in args[2:-1]) + args[-1:]
    n = 0
    for e in range(1, srv.tree_expansions + 1):
        jax.block_until_ready(srv._casc_draft_fn(e)(*args))
        n += 1
    # the plain tree verify (no slot routed through the rescorer)
    tv_fn = srv._tree_verify
    _, tv_args = ex["rescore_verify"]
    # rescore_verify args: (lvl params, params, cache, tok, par, dep, pac,
    # msk, cnt, ...) -> tree verify takes (params, cache, tok, par, dep,
    # msk, cnt, live)
    tok, par, dep, _pac, msk, cnt = (_host(a) for a in tv_args[3:9])
    live = _host(np.zeros((srv.B,), bool))
    cache = jax.tree.map(lambda a: jax.device_put(jnp.copy(a), a.sharding),
                         srv.cache)
    jax.block_until_ready(tv_fn(srv.params, cache, tok, par, dep, msk, cnt,
                                live))
    return n + 1
