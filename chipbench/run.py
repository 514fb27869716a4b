#!/usr/bin/env python3
"""Run one cell of the on-chip benchmark once and print its result line.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one cell, one run, on the chip it is started on. It exits
non-zero, with no result, where JAX finds no TPU or fewer chips than the
cell asks for. ``--trace 0`` reports the cell's end-to-end metrics;
``--trace 1`` reports the per-layer ones: it records a profiler trace of one
round before the window, for the device's numbers, and takes the host's
spans in the window, where the profiler's per-op collection does not slow
the host loop. The last line of standard output is the result; the
numbers that decided ``correct`` are also the last lines of standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

REPO = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(REPO), str(REPO / "src")]


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class Tracer:
    """The profiler around the traced round, in a temporary directory, with
    that round marked as the host span ``window`` on the trace's clock."""

    def __init__(self, jax):
        self.jax = jax
        self.dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        self._ann = None

    def start(self) -> None:
        opts = self.jax.profiler.ProfileOptions()
        # Python calls and the runtime's own host events stay out of the
        # trace (they slow the host loop); the harness's spans stay in
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        self.jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._ann = self.jax.profiler.TraceAnnotation("chipbench:window")
        self._ann.__enter__()

    def stop(self) -> None:
        self._ann.__exit__(None, None, None)
        self.jax.profiler.stop_trace()

    def load(self):
        from chipbench import trace
        files = sorted(Path(self.dir).rglob("*.xplane.pb"))
        return trace.load(str(files[-1]))

    def close(self) -> None:
        shutil.rmtree(self.dir, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from chipbench import harness
    import repro  # noqa: F401  (the system under test; absent, nothing runs)
    man = harness.manifest()
    cell = harness.find_cell(man, args.workload)
    cfg = harness.load_json("configs", cell["config"])
    traffic = harness.load_json("traffic", cell["traffic"])
    limits = harness.load_json("checks", cell["name"])["limits"]

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        log(f"no TPU: jax.devices()[0] is {dev.platform} ({dev.device_kind});"
            f" the benchmark runs on the chip only")
        return 1
    if len(devices) < cell["chips"]:
        log(f"{cell['name']} needs {cell['chips']} chips, JAX finds "
            f"{len(devices)}")
        return 1
    peaks = harness.peaks(dev.device_kind)
    from repro.launch.cache import enable_compile_cache
    # every program, however quick to compile, goes to the cache, so that
    # only a checkout's first run of a cell compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    log(f"compile cache: {enable_compile_cache()}")
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; host "
        f"MemTotal {harness.mem_total_bytes()} B; jax {jax.__version__}")
    result = run_cell(jax, man, cell, cfg, traffic, limits, peaks, args)
    print(json.dumps(result), flush=True)
    return 0


def run_cell(jax, man, cell, cfg, traffic, limits, peaks, args) -> dict:
    from chipbench import harness, trace

    compiles = harness.CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(compiles)

    cfg_mod = harness.load_module("configs", cell["config"])
    driver = harness.load_module("drivers", traffic["driver"])
    tracer = Tracer(jax) if args.trace else None
    try:
        out = driver.drive(cfg, traffic, args.seed, args.seconds,
                           traced=bool(args.trace), compiles=compiles,
                           log=log, t_start=T_START, tracer=tracer)
        run = out["run"]
        run.peaks = peaks
        run.flops_per_round = cfg_mod.round_flops(out["work"])
        dev = jax.devices()[0]
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": cell["chips"],
                  "memory_peak_bytes": run.peak_hbm_bytes}
        breakdown = None
        if tracer is not None:
            tr = tracer.load()
            lo, hi = tr.window()
            run.trace, run.trace_lo, run.trace_hi = tr, lo, hi
            summary = trace.device_summary(tr, list(range(cell["chips"])),
                                           lo, hi)
            device["busy_s"] = summary["busy_s"]
            device["window_s"] = summary["window_s"]
            breakdown = summary["breakdown"]
    finally:
        if tracer is not None:
            tracer.close()

    metrics = {}
    for m in harness.metrics_of(man, cell["name"], bool(args.trace)):
        value = harness.load_module("metrics", m["name"]).read(run)
        if harness.finite(value):
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        else:
            log(f"metric {m['name']}: nothing to read in this run")

    correct, checks = harness.decide(driver.compare(out, cfg_mod, args.seed),
                                     limits)
    for k, c in checks.items():
        log(f"check {k}: {c['value']!r} (limit {c['limit']!r})")
    result = {"correct": correct, "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


if __name__ == "__main__":
    sys.exit(main())
