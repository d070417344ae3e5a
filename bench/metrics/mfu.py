"""The whole step's share of the chip's bf16 peak: target-forward
operations of the tokens delivered in the window (every projection, the
head, and attention over each token's context, from the configuration's
shapes) per second, over the peak of the chips used."""


def read(run):
    if not run.flops:
        return None
    peak = run.peaks()["bf16_flops_s"] * run.device["count"]
    return 100.0 * run.flops / run.win.seconds / peak
