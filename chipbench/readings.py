"""Read the numbers that decide ``correct`` for the program and for its
control (the reference one precision step lower) on many seeds, in one
process, at the cell's own sizes.  The limits in the workload files were set
from these readings.

    python3 chipbench/readings.py --workload <name> --seeds 1,2,3

Prints one JSON line per seed and a summary last.  The benchmark's own runs
never call this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]

from chipbench import catalog  # noqa: E402
from chipbench.run import check_devices, enable_compile_cache  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    args = ap.parse_args(argv)
    cell = catalog.load_cell(args.workload)
    check_devices(cell.chips)
    enable_compile_cache()
    driver = catalog.load_driver(cell.driver)
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        got = driver.readings(cell, seed)
        rows.append(got)
        print(json.dumps({"seed": seed, "seconds": time.perf_counter() - t, **got}),
              flush=True)
    summary = {side: {k: {"max": max(r[side][k] for r in rows),
                          "min": min(r[side][k] for r in rows)}
                      for k in rows[0][side]} for side in rows[0]}
    print(json.dumps({"workload": cell.name, "seeds": len(rows), "summary": summary}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
