#!/usr/bin/env python3
"""Readings that the correctness limits are set from (not run by a check).

    python3 bench/calibrate.py --workload <cell> --seeds 12 --control 3 \
        --seconds 20 [--first-seed N]

In one process, for each seed: the cell's weights and inputs from that
seed, a short window at the cell's own load, and the number the run
compares (``lower``).  For the first ``--control`` seeds it also reads the
control on the same requests: the reference computed in float8 in the
program's place (``upper``).  One JSON line per seed, then a summary.
"""

import argparse
import gc
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT / "bench")]


def readings(sut, seed: int, limits: dict, control: bool = False) -> dict:
    """Every statistic a limits file may compare, for the program or, with
    ``control``, for the float8 reference in the program's place."""
    g = sut.gaps(seed, limits, control=control)
    return {k: float(fn(g)) for k, fn in sut.GAP_STATS.items()}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--first-seed", type=int, default=2 ** 31 + 1000)
    a = ap.parse_args()
    import run
    from harness import device
    c = run.load_cell(a.workload)
    run.configure_jax()
    device.require(c.cell["chips"])
    rows = []
    for i in range(a.seeds):
        seed = a.first_seed + 7919 * i
        t = time.perf_counter()
        sut = run.system(c, seed)
        sut.setup()
        sut.window(a.seconds)
        sut.free()
        row = {"seed": seed, "lower": readings(sut, seed, c.limits),
               "attempted": sut.counters().get("attempted")}
        if i < a.control:
            row["upper"] = readings(sut, seed, c.limits, control=True)
        row["seconds"] = time.perf_counter() - t
        print(json.dumps(row), flush=True)
        rows.append(row)
        del sut
        gc.collect()
    for k in rows[0]["lower"]:
        ups = [r["upper"][k] for r in rows if "upper" in r]
        print(json.dumps({"workload": a.workload, "number": k,
                          "lower_max": max(r["lower"][k] for r in rows),
                          "upper_min": min(ups) if ups else None}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
