"""Benchmark harness: one module per paper table/figure + roofline.

Prints ``name,value,derived`` CSV rows. Usage:
  PYTHONPATH=src python -m benchmarks.run            # all benches
  PYTHONPATH=src python -m benchmarks.run table1 fig7
"""
from __future__ import annotations

import sys
import time

from benchmarks import (edgebench, fig7_byzantine, kernelbench, netbench,
                        roofline, table1_collab, table5_runs, table6_edge,
                        table7_overhead)

BENCHES = {
    "table1": table1_collab.main,     # No-Collab vs Collab (paper Table 1)
    "table5": table5_runs.main,       # GPU-cluster run matrix (Table 5)
    "table6": table6_edge.main,       # edge cluster Sync/Async (Table 6)
    "table7": table7_overhead.main,   # system overhead (Table 7)
    "fig7": fig7_byzantine.main,      # byzantine policies (Figure 7)
    "kernels": kernelbench.main,      # paper hot-spot kernels
    "net": netbench.main,             # store-network WAN fabric scenarios
    "edge": edgebench.main,           # hierarchical fleets + light clients
    "roofline": roofline.main,        # dry-run roofline table (§Roofline)
}


def main() -> None:
    picks = [a for a in sys.argv[1:] if a in BENCHES] or list(BENCHES)
    print("name,value,derived")
    t0 = time.time()
    failed = []
    for name in picks:
        try:
            BENCHES[name]()
        except Exception as e:  # report, run the rest, then fail the run
            print(f"{name}_ERROR,1,{e!r}")
            failed.append(name)
    print(f"total_wall_s,{time.time() - t0:.1f},{len(picks)} benches")
    if failed:
        sys.exit(f"benchmarks failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
