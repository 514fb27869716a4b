"""The fused int8 weighted sum (``kernels/q8agg.wsum_q8``) against its
roofline: for each call in the window, the least time the chip could take
(the larger of its bytes over HBM bandwidth and its operations over the
bf16 peak, from the call's shapes; ``q8ref.wsum_q8_cost``), summed, over
the summed device time of the kernel's events in the trace."""
from chipbench import trace

EVENTS = r"^wsum_q8(\.\d+)?$"    # the Pallas call, named after its function


def read(run):
    calls = run.kernel_calls.get("wsum_q8")
    if run.trace is None or not calls:
        return None
    spent = trace.kernel_s(run.trace.device_ops.get(0, []), EVENTS,
                           run.trace_lo, run.trace_hi)
    if spent <= 0:
        return None
    p = run.peaks
    least = sum(max(b / p["hbm_bytes_per_s"], ops / p["bf16_flops"])
                for b, ops in calls)
    return 100.0 * least / spent
