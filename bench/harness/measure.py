"""End-to-end numbers of one window, from the host timestamps the loop
recorded on each request (``Request.arrivals``: one (time, cumulative
tokens) entry per delivered batch of tokens).

Only what was delivered inside the window counts: a batch of tokens that
arrives after the window closed is not in any metric.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np


def delivered(rec, t_end: float) -> List[tuple]:
    """(time, cumulative tokens) of the batches delivered by ``t_end``,
    capped at the request's budget (overshoot is trimmed at retire)."""
    cap = rec.request.max_new_tokens
    out, last = [], 0
    for t, cum in rec.request.arrivals:
        if t > t_end or last >= cap:
            break
        last = min(cum, cap)
        out.append((t, last))
    return out


def pct(values, q: float) -> Optional[float]:
    return float(np.percentile(values, q)) if len(values) else None


def end_to_end(win) -> Dict[str, object]:
    """Tokens delivered in the window, and every gap, in seconds, between
    two delivered batches of one request (``itl``)."""
    tokens, gaps = 0, []
    for rec in win.records:
        d = delivered(rec, win.t_end)
        if d:
            tokens += d[-1][1]
            ts = [t for t, _ in d]
            gaps += [b - a for a, b in zip(ts, ts[1:])]
    return {"tokens": tokens, "itl": gaps}


def ms(values, q: float) -> Optional[float]:
    """The ``q``-th percentile of samples in seconds, in milliseconds."""
    x = pct(values, q)
    return None if x is None else x * 1e3


def summary(e2e: dict, seconds: float) -> Dict[str, object]:
    """The window's end-to-end numbers, for the readings tool."""
    return {"out_tok_s": e2e["tokens"] / seconds,
            "itl_p99_ms": ms(e2e["itl"], 99),
            "delivered_tokens": e2e["tokens"],
            "itl_samples": len(e2e["itl"])}


def flops_per_token(c: dict, position: int) -> float:
    """Target-forward operations for one token at ``position``: every
    projection and the head (2 per weight), and attention's scores and
    mix over ``position + 1`` keys in every layer."""
    d, f, L = c["hidden_size"], c["intermediate_size"], c["num_hidden_layers"]
    H, KV, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    per_layer = d * (H + 2 * KV) * hd + H * hd * d + (3 if c["mlp_gated"]
                                                      else 2) * d * f
    return (2.0 * (L * per_layer + d * c["vocab_size"])
            + 4.0 * L * H * hd * (position + 1))


def delivered_flops(c: dict, win) -> float:
    total = 0.0
    for rec in win.records:
        d = delivered(rec, win.t_end)
        if d:
            p = len(rec.offered.prompt)
            total += sum(flops_per_token(c, p + i) for i in range(d[-1][1]))
    return total
