"""One run of a benchmark cell at a size a CPU test can hold, in a process
of its own (the run makes its mesh global).

    JAX_PLATFORMS=cpu python bench/tests/tiny_run.py <mode> \
        [sound|altered|stuck|int8|fp8]

Prints the run's result as one JSON line. ``altered`` and ``stuck`` break
the timed path first; ``int8`` and ``fp8`` put the reference at that
precision in the program's place for the correctness check. The
configuration is ``sc2-3b.cascade`` cut to 4 layers of width 256 (mode
``tree_fused`` uses that server mode instead); the mix is
``decode-batch`` cut to short prompts and outputs.
"""
import argparse
import copy
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import run as R  # noqa: E402
from harness import registry  # noqa: E402

WORKLOAD = "sc2-3b.decode-batch"


def tiny(mode: str):
    c = copy.deepcopy(registry.load_json("configs", "sc2-3b.cascade"))
    c.update(hidden_size=256, intermediate_size=512, num_hidden_layers=4,
             num_attention_heads=4, num_key_value_heads=2, head_dim=64,
             vocab_size=500)
    c["server"].update(max_batch=2, max_len=192, mode=mode)
    if mode != "cascade_fused":
        c["server"]["prefill_chunk"] = 16
    mix = copy.deepcopy(registry.load_json("mixes", "decode-batch"))
    mix.update(block=4, requests=12)
    mix["prompt"].update(mean=24)
    mix["output"].update(mean=16)
    return c, mix


def alter_tokens(srv, loop):
    """The fault: every round's output has its last token changed where
    the server produces it."""
    step = srv.step
    vocab = srv.cfg.vocab_size

    def bad_step():
        out = step()
        return {s: t[:-1] + [(t[-1] + 1) % vocab] if t else t
                for s, t in out.items()}

    srv.step = bad_step


def commit_nothing(srv, loop):
    """The fault: a round that leaves every slot's state as it was (no
    token committed, none delivered)."""
    srv.step = lambda: {}
    srv.flush = lambda: {}


FAULTS = {"altered": alter_tokens, "stuck": commit_nothing}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("mode")
    ap.add_argument("what", nargs="?", default="sound")
    a = ap.parse_args()
    import jax

    c, mix = tiny(a.mode)
    hooks = {"devices": lambda jax, n: jax.devices()[:n], "jax": lambda: jax,
             "c": c, "mix": mix}
    args = argparse.Namespace(workload=WORKLOAD, seed=2**31 + 11,
                              seconds=4.0, trace=0)
    if a.what in ("int8", "fp8"):
        hooks["control"] = a.what
    if a.what in FAULTS:
        hooks["break"] = FAULTS[a.what]
    print(json.dumps(R.run(args, hooks)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
