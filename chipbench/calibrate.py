#!/usr/bin/env python3
"""Readings that the limits of a cell's ``correct`` are set from.

    python chipbench/calibrate.py --workload <cell> --seeds 1-12 \
        [--control 3] [--fault-seeds 3] [--faults a,b] [--seconds 51] \
        [--fault-seconds 1] [--out file.json]

In one process, on the chip: the program's numbers on each seed (sound
runs, the lower readings), the control's on the first ``--control`` of
them (the reference in the precision below the configuration's, put in the
program's place), and each planted fault of the cell's driver on
``--fault-seeds`` seeds (all the driver's faults, or those ``--faults``
names). Each run is the cell's own set-up and a window at the cell's own
size: ``--seconds`` long for the sound runs and the control, so that they
compare as many rounds as a run of the cell does, and ``--fault-seconds``
for the faults, which need only fail. The benchmark's runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(REPO), str(REPO / "src")]


def seeds_of(text: str):
    lo, _, hi = text.partition("-")
    return list(range(int(lo), int(hi or lo) + 1))


def readings(cell_name: str, seeds, n_control: int, fault_seeds: int,
             seconds: float, fault_seconds: float, faults=None, *,
             log=print) -> dict:
    import jax
    from chipbench import harness

    man = harness.manifest()
    cell = harness.find_cell(man, cell_name)
    cfg = harness.load_json("configs", cell["config"])
    traffic = harness.load_json("traffic", cell["traffic"])
    cfg_mod = harness.load_module("configs", cell["config"])
    driver = harness.load_module("drivers", traffic["driver"])
    compiles = harness.CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    quiet = lambda msg: None

    def one(seed, fault=None):
        out = driver.drive(cfg, traffic, seed,
                           seconds if fault is None else fault_seconds,
                           traced=False,
                           compiles=compiles, log=quiet,
                           t_start=time.perf_counter(), fault=fault)
        return out, driver.compare(out, cfg_mod, seed)

    table = {"cell": cell_name, "sound": {}, "control": {}, "faults": {}}
    for i, seed in enumerate(seeds):
        out, nums = one(seed)
        table["sound"][seed] = nums
        log(f"seed {seed}: {json.dumps(nums)}")
        if i < n_control:
            table["control"][seed] = driver.control(out, cfg_mod, seed)
            log(f"  control: {json.dumps(table['control'][seed])}")
    for fault in faults or driver.FAULTS:
        for seed in seeds[:fault_seeds]:
            _, nums = one(seed, fault)
            table["faults"].setdefault(fault, {})[seed] = nums
            log(f"fault {fault} seed {seed}: {json.dumps(nums)}")
    names = list(next(iter(table["sound"].values())))
    table["lower"] = {k: max(v[k] for v in table["sound"].values())
                      for k in names}
    table["control_least"] = {k: min(v[k] for v in table["control"].values())
                              for k in names if table["control"]
                              and k in next(iter(table["control"].values()))}
    table["fault_least"] = {f: {k: min(v[k] for v in rows.values())
                                for k in names}
                            for f, rows in table["faults"].items()}
    return table


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1-12")
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--fault-seeds", type=int, default=3)
    ap.add_argument("--faults", default="",
                    help="comma-separated; default: every fault of the driver")
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--fault-seconds", type=float, default=1.0)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("no TPU: calibration reads the chip's numbers", file=sys.stderr)
        return 1
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    table = readings(args.workload, seeds_of(args.seeds), args.control,
                     args.fault_seeds, args.seconds, args.fault_seconds,
                     [f for f in args.faults.split(",") if f])
    text = json.dumps(table, indent=1)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(text)
    print(json.dumps({k: table[k] for k in
                      ("lower", "control_least", "fault_least")}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
