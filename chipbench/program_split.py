#!/usr/bin/env python3
"""The program's own wall spans over a cell's window: where local training's
host time goes (feed, dispatch, wait), peer fetches, host syncs and H2D
bytes.

    python chipbench/program_split.py --workload <cell> --seed <n> \\
        --seconds <s> --wall <0|1>

One run of the cell as ``run.py --trace 1`` makes it (the profiler over the
round before the window, the harness's spans in the window), with the
program's wall (``repro.obs.wall``, switched on through
``ObsConfig(wall=True)``) on, or with ``--wall 0`` left off. It prints one
JSON line: the window's ``round_s``; per round, the harness's layer self
times and the wall's totals; the numbers that per-layer metrics of the
program's spans would read (``per_round``); and, in the traced round, the
device idle seconds by the innermost program span, and harness span, the
host was in, and the device seconds of each named kernel's ops. ``--wall 0`` against ``--wall 1`` on one seed is what the
wall costs a round.

The cells' own runs leave the wall off: switching it on and reading it over
the window is the driver's to do (PERF.md, Open questions). Until then this
script reaches the window through the driver's ``Window``, which it replaces
with one that takes the wall's totals when the window opens and when its
last round ends. It refuses a host without a TPU, as ``run.py`` does.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Sequence, Tuple  # noqa: E402

REPO = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(REPO), str(REPO / "src")]

from chipbench import harness, trace  # noqa: E402

PREFIX = "unifyfl:"       # the program's spans in a profiler trace
# the device ops of the kernels that name their pallas_call
KERNEL_OPS = re.compile(r"^(wsum_q8|add_q8_delta|gram_q8|quantize|dequantize"
                        r"|dequantize_batch)(\.\d+)?$")
# what a per-layer metric of the program's spans reads: a wall total, scaled
PER_ROUND = {
    "train_feed_s_per_round": ("train.feed_s", 1.0),
    "train_dispatch_s_per_round": ("train.dispatch_s", 1.0),
    "train_wait_s_per_round": ("train.wait_s", 1.0),
    "fetch_s_per_round": ("exchange.fetch_s", 1.0),
    "host_syncs_per_round": ("host_syncs", 1.0),
    "h2d_gb_per_round": ("h2d_bytes", 1e-9),
}


def load_spans(path: str) -> List[trace.Event]:
    """The program's spans (``unifyfl:<name>`` annotations) in an
    ``.xplane.pb`` file, by start, named without the prefix."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if trace.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            out.extend(trace.Event(e.name[len(PREFIX):], e.start_ns * 1e-9,
                                   e.duration_ns * 1e-9)
                       for e in line.events if e.name.startswith(PREFIX))
    return sorted(out, key=lambda e: e.start)


def innermost(spans: Sequence[trace.Event], lo: float, hi: float
              ) -> List[Tuple[float, float, str]]:
    """[lo, hi] cut into pieces, each named by the innermost span that
    covers it (``other`` where none does). Spans of one thread nest, so the
    innermost is the one opened last that is still open."""
    cuts = sorted({lo, hi, *(t for s in spans for t in (s.start, s.end)
                             if lo < t < hi)})
    spans = sorted(spans, key=lambda s: (s.start, -s.dur))
    pieces, opened, i = [], [], 0
    for a, b in zip(cuts, cuts[1:]):
        while i < len(spans) and spans[i].start <= a:
            opened.append(spans[i])
            i += 1
        while opened and opened[-1].end <= a:
            opened.pop()
        pieces.append((a, b, opened[-1].name if opened else "other"))
    return pieces


def idle_by_span(ops: Sequence[trace.Event], spans: Sequence[trace.Event],
                 lo: float, hi: float) -> Dict[str, float]:
    """Device idle seconds in [lo, hi] by the innermost span the host was
    in: a gap that runs from one span into the next is split between them
    (the harness's ``breakdown`` gives each gap whole to one span; a gap of
    a training step runs through the end of one step's wait, the next
    step's feed and its dispatch)."""
    pieces = innermost([s for s in spans if s.name != "window"], lo, hi)
    idle: Dict[str, float] = {}
    j = 0
    for a, b in trace.gaps(ops, lo, hi):
        while pieces[j][1] <= a:
            j += 1
        for x, y, name in pieces[j:]:
            if x >= b:
                break
            idle[name] = idle.get(name, 0.0) + min(b, y) - max(a, x)
    return idle


def per_round(delta: Dict[str, float], rounds: int) -> Dict[str, float]:
    return {name: delta[key] * scale / rounds
            for name, (key, scale) in PER_ROUND.items()}


def measure(cfg: Dict, traffic: Dict, seed: int, seconds: float, *,
            wall_on: bool, tracer, log, t_start: float) -> Dict:
    """One traced run of the cell through its driver, with the program's
    wall on or off, reduced to the split."""
    from repro.config import ObsConfig
    from repro.obs import wall

    driver = harness.load_module("drivers", traffic["driver"])
    traffic = dict(traffic, fed=dict(traffic["fed"],
                                     obs=ObsConfig(wall=wall_on)))
    snaps: Dict[str, Dict[str, float]] = {}

    class Window(harness.Window):
        def open(self, t: float) -> None:
            super().open(t)
            snaps["open"] = wall.totals()

        def round_ended(self, t: float) -> bool:
            more = super().round_ended(t)
            if not more:
                snaps["close"] = wall.totals()
            return more

    with harness.Patches() as p:
        p.set(driver, "Window", Window)
        out = driver.drive(cfg, traffic, seed, seconds, traced=True,
                           compiles=harness.CompileCounter(), log=log,
                           t_start=t_start, tracer=tracer)
    wall.switch(False)
    run = out["run"]
    delta = wall.diff(snaps["open"], snaps["close"])
    path = str(sorted(Path(tracer.dir).rglob("*.xplane.pb"))[-1])
    tr = trace.load(path)
    lo, hi = tr.window()
    ops = trace.clip(tr.device_ops.get(0, []), lo, hi)
    kernels: Dict[str, float] = {}
    for e in ops:
        if KERNEL_OPS.match(e.name):
            kernels[e.name] = kernels.get(e.name, 0.0) + e.dur
    return {
        "wall": wall_on, "seed": seed, "failed": out["failed"],
        "rounds": run.rounds, "round_s": run.round_s,
        "harness_s_per_round": {k: v / run.rounds
                                for k, v in run.spans.self_s.items()},
        "wall_per_round": {k: v / run.rounds for k, v in delta.items()},
        "per_round": per_round(delta, run.rounds) if wall_on else {},
        "traced_round": {
            "window_s": hi - lo, "busy_s": trace.busy_s(ops, lo, hi),
            "idle_by_program_span": idle_by_span(ops, load_spans(path),
                                                 lo, hi),
            "idle_by_harness_span": idle_by_span(ops, tr.spans, lo, hi),
            "kernel_s": kernels},
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--wall", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)

    man = harness.manifest()
    cell = harness.find_cell(man, args.workload)
    cfg = harness.load_json("configs", cell["config"])
    traffic = harness.load_json("traffic", cell["traffic"])

    import jax
    if jax.devices()[0].platform != "tpu":
        print("no TPU: the split is measured on the chip only",
              file=sys.stderr)
        return 1
    spec = importlib.util.spec_from_file_location(
        "chipbench_run", harness.HERE / "run.py")
    run_mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run_mod)
    tracer = run_mod.Tracer(jax)
    try:
        result = measure(cfg, traffic, args.seed, args.seconds,
                         wall_on=bool(args.wall), tracer=tracer,
                         log=run_mod.log, t_start=T_START)
    finally:
        tracer.close()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
