"""Percent of a round in which no operation ran on the device: the union of
the op intervals in the traced round (averaged over the chips used), over
the seconds a round of the window takes. The window's rounds run without
the profiler, whose per-op collection slows the host loop; the device's own
work is the same in every round."""
from chipbench import trace


def read(run):
    if run.trace is None:
        return None
    busy = [trace.busy_s(run.trace.device_ops.get(c, []), run.trace_lo,
                         run.trace_hi) for c in range(run.chips)]
    if not any(busy):
        return None
    return 100.0 * (1.0 - sum(busy) / len(busy) / run.round_s)
