"""Measure a cell the way the driver does: sets of runs, each run a new
process with another seed, and for every metric the median and the
spread (the distance between the quartiles over the median). A bound is
about five times the wider of two sets' spreads, and never under 1 %.

    python3 -m benchmark.tools.measure --workload small-train-1k --sets 2 --runs 6 [--seconds 20] [--trace 0] [--first-seed 100]

On the chip, in one call (`chiprun -- python3 -m benchmark.tools.measure
...`). This process never touches jax: a chip belongs to one process at
a time. Every run's last line is kept in `chiprun_out/measure/`.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def spread(values) -> float:
    if len(values) < 2:
        return 0.0
    q = statistics.quantiles(values, n=4, method="inclusive")
    med = statistics.median(values)
    return (q[2] - q[0]) / med if med else float("inf")


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--sets", type=int, default=2)
    parser.add_argument("--runs", type=int, default=6)
    parser.add_argument("--seconds", type=float, default=None)
    parser.add_argument("--trace", type=int, default=0)
    parser.add_argument("--first-seed", type=int, default=100)
    args = parser.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    seconds = args.seconds or spec["run_seconds"]
    out_dir = os.path.join(ROOT, "chiprun_out", "measure")
    os.makedirs(out_dir, exist_ok=True)
    seed = args.first_seed
    sets = []
    for s in range(args.sets):
        rows = []
        for _ in range(args.runs):
            cmd = spec["command"] + [
                "--workload", args.workload, "--seed", str(seed),
                "--seconds", str(seconds), "--trace", str(args.trace)]
            t0 = time.time()
            proc = subprocess.run(cmd, cwd=ROOT, stdout=subprocess.PIPE,
                                  stderr=subprocess.PIPE, text=True)
            took = time.time() - t0
            lines = proc.stdout.strip().splitlines()
            tag = f"{args.workload}.t{args.trace}.seed{seed}"
            with open(os.path.join(out_dir, tag + ".out"), "w") as f:
                f.write(proc.stdout)
            if proc.returncode != 0 or not lines:
                with open(os.path.join(out_dir, tag + ".err"), "w") as f:
                    f.write(proc.stderr)
                print(f"run seed {seed}: exit {proc.returncode}\n"
                      + proc.stderr[-2000:], flush=True)
                seed += 1
                continue
            result = json.loads(lines[-1])
            extra = json.loads(lines[-2]) if len(lines) > 1 else {}
            rows.append(result)
            print(json.dumps({
                "set": s, "seed": seed, "took_s": round(took, 1),
                "correct": result["correct"],
                "attempted": result["attempted"], "failed": result["failed"],
                "compiles_in_window": extra.get("compiles_in_window"),
                "cache": extra.get("compile_cache", {}),
                "memory_peak_bytes": result["device"]["memory_peak_bytes"],
                **{k: v["value"] for k, v in result["metrics"].items()},
            }), flush=True)
            seed += 1
        sets.append(rows)
    print("--- medians and spreads (first run of set 0 kept: it may "
          "compile; see setup_s) ---")
    names = sorted({k for rows in sets for r in rows for k in r["metrics"]})
    for name in names:
        line = {"metric": name}
        for s, rows in enumerate(sets):
            vals = [r["metrics"][name]["value"] for r in rows
                    if name in r["metrics"]]
            if name == "setup_s" and s == 0:
                vals = vals[1:]
            if vals:
                line[f"set{s}"] = {"median": statistics.median(vals),
                                   "spread": round(spread(vals), 5),
                                   "n": len(vals)}
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
