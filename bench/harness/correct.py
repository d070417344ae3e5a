"""Whether the window's output is correct.

After the window has closed (and the program's state is freed), a sample
of the requests the window finished, drawn from the seed and always
holding the longest, is run through the plain reference once each: the
prompt followed by the served tokens. For every served token the gap is
the reference's best logit minus the reference's logit of the served
token (0 where they agree). The number compared is the mean gap over the
sample's served tokens; its limit is the cell's
(``limits/<workload>.json``). The widest gap is logged beside it: with
random weights the logits are nearly flat, and the program's bf16
near-tie flips come within a few times of a lower-precision control's,
while the mean over a few thousand tokens stays far apart. Every finished request must also carry exactly its
budget of tokens, all inside the vocabulary.

The control puts the reference in the program's place at a lower
precision: at the same positions of the same sequences the token that the
lower precision ranks first is scored in place of the served one, and the
same limits judge it.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

SAMPLE_TOKENS = 4000         # served tokens the sample holds at least


def sample(records: list, seed: int) -> list:
    done = [r for r in records if r.request.done]
    if not done:
        return []
    done.sort(key=lambda r: r.offered.index)
    longest = max(done, key=lambda r: len(r.offered.prompt)
                  + len(r.request.generated))
    rng = np.random.default_rng(seed)
    picked, n = [longest], len(longest.request.generated)
    for i in rng.permutation(len(done)):
        if n >= SAMPLE_TOKENS:
            break
        r = done[int(i)]
        if r is not longest:
            picked.append(r)
            n += len(r.request.generated)
    return picked


def shape_faults(records: list, vocab: int) -> List[str]:
    """Finished requests with a wrong token count or a token outside the
    vocabulary."""
    out = []
    for r in records:
        if not r.request.done:
            continue
        g = r.request.generated
        if len(g) != r.request.max_new_tokens:
            out.append(f"request {r.offered.index}: {len(g)} tokens, "
                       f"budget {r.request.max_new_tokens}")
        elif any(not 0 <= t < vocab for t in g):
            out.append(f"request {r.offered.index}: token outside the "
                       "vocabulary")
    return out


def gaps(ref, params, seqs: list, control=None) -> Dict[str, object]:
    """``seqs``: (prompt, served tokens) pairs. Scores the served tokens,
    or, with a ``control`` reference, the token the control ranks first at
    each position of the same sequences (the control in the program's
    place). Returns the mean and the widest gap, each request's summed
    gap, tokens and widest gap, and the number of scored tokens that
    differ from the reference's argmax."""
    per, differ, n, total = [], 0, 0, 0.0
    for prompt, served in seqs:
        served = np.asarray(served, np.int32)
        toks = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        scored = served
        if control is not None:
            scored = control.score(params, toks, len(prompt) - 1, served)[2]
        best, at, arg = ref.score(params, toks, len(prompt) - 1, scored)
        gap = best - at
        per.append([float(np.sum(gap)), len(served), float(np.max(gap))])
        total += float(np.sum(gap))
        differ += int(np.sum(arg != scored))
        n += len(served)
    return {"widest_gap": max((w for _, _, w in per), default=0.0),
            "per_request": per, "mean_gap": total / max(n, 1),
            "differ": differ, "tokens": n}
