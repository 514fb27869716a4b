"""The batched dequantize of the scoring engine's q8-direct ingest
(``kernels/quant.dequantize_batch``) against its roofline: for each call in
the traced round, the least time the chip could take (the larger of its
bytes over HBM bandwidth and its operations over the bf16 peak, from the
call's shapes; ``dequant_cost.dequantize_batch_cost``), summed, over the
summed device time of the kernel's events in the trace."""
from chipbench import trace

EVENTS = r"^dequantize_batch(\.\d+)?$"   # the Pallas call, named after it


def read(run):
    calls = run.kernel_calls.get("dequantize_batch")
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
