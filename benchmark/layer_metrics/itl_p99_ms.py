"""99th percentile of every gap between consecutive tokens of a request
(a decode iteration stalled behind a prefill shows here, not in the
median)."""
from benchmark.loadgen import quantile

LAYER = "engine"
UNIT = "ms"
SOURCE = "host_clock"


def read(run):
    r = run.records
    if r["kind"] != "serve":
        return None
    gaps = [b - a for q in r["requests"]
            for a, b in zip(q["token_times"], q["token_times"][1:])]
    return 1e3 * quantile(gaps, 0.99) if gaps else None
