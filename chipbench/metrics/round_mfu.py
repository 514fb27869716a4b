"""The whole round's share of the chips' bf16 peak: the FLOPs that the
round's training (forward and backward, no recompute) and scoring forwards
require, from the configuration's shapes, over the window's seconds per
round (rounds run without the profiler) x chips x peak."""


def read(run):
    if run.trace is None or not run.flops_per_round:
        return None
    return 100.0 * run.flops_per_round / (
        run.round_s * run.chips * run.peaks["bf16_flops"])
