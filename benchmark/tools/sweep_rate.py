"""Find a serving cell's knee once, on the chip: one engine, one window
of the cell's mix at each of several offered rates (open loop) or client
counts (closed loop), in one process.

    python3 -m benchmark.tools.sweep_rate --workload small-serve-chat --rates 20,40,60,80 --seconds 10

The knee is the highest rate at which the queue does not grow through
the window (completed ~ offered, nothing shed, `queued_at_close` small,
TTFT not climbing); the cell then runs at about four fifths of it, and
the number goes into the traffic file. This is a tool for the builder of
a `benchmark` PR; the driver never runs it, and what it prints is not a
metric.
"""
from __future__ import annotations

import argparse
import json
import sys

from benchmark import loadgen
from benchmark import run as run_mod
from benchmark.drivers import serve
from benchmark.end_to_end import itl_p50_ms, ttft_p95_ms


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--rates", required=True,
                        help="offered requests/s (open) or clients (closed)")
    parser.add_argument("--seconds", type=float, default=10.0)
    parser.add_argument("--seed", type=int, default=7)
    parser.add_argument("--set", action="append", default=[],
                        help="a.b=value: override a key of the traffic file")
    args = parser.parse_args()
    import jax

    from determined_tpu.common import compile_cache

    cell = run_mod.Cell(args.workload)
    for item in args.set:
        path, _, value = item.partition("=")
        node = cell.traffic
        *parents, leaf = path.split(".")
        for p in parents:
            node = node[p]
        node[leaf] = json.loads(value)
    if jax.devices()[0].platform != "tpu":
        print("sweep_rate: no TPU", file=sys.stderr)
        return 2
    compile_cache.enable()

    def harness(traffic):
        cell.traffic = traffic
        return run_mod.Harness(cell, args.seed, args.seconds, False,
                               jax.devices()[:1], "/dev/null")

    engine, _params = serve.build_engine(harness(cell.traffic))
    base = cell.traffic
    for value in (float(x) for x in args.rates.split(",")):
        if base["loop"] == "open":
            traffic = {**base, "arrivals": {**base["arrivals"],
                                            "rate_per_s": value}}
        else:
            traffic = {**base, "clients": int(value)}
        r = serve.measure(harness(traffic), engine)
        r.pop("served")
        ttft = ttft_p95_ms.ttfts_s(r) if r["requests"] else []
        gaps = itl_p50_ms.mean_gaps_s(r)
        window = r["t1"] - r["t0"]
        print(json.dumps({
            "offered": value, "attempted": r["attempted"],
            "failed": r["failed"], "outcomes": r["notes"]["outcomes"],
            "completed_per_s": (r["attempted"] - r["failed"]) / window,
            "tokens_per_s": r["tokens_in_window"] / window,
            "ttft_ms_p50": 1e3 * loadgen.quantile(ttft, 0.5) if ttft else None,
            "ttft_ms_p95": 1e3 * loadgen.quantile(ttft, 0.95) if ttft else None,
            "itl_ms_p50": 1e3 * loadgen.quantile(gaps, 0.5) if gaps else None,
            "queued_at_close": r["notes"]["queued_at_close"],
            "late_ms_max": r["notes"]["generator_late_ms_max"],
            "tokens_per_iter": r["counters"]["tokens"]
            / max(r["counters"]["decode_iterations"], 1),
            "decode_iter_ms": 1e3 * r["counters"]["decode_iter_seconds"]
            / max(r["counters"]["decode_iter_count"], 1),
        }), flush=True)
    engine.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
