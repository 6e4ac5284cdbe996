"""Median over requests of (last token - first token) / (tokens - 1):
the gap between tokens a reader of the stream feels. A stream the run
cut short when it stopped counts with the tokens it had."""
from benchmark.loadgen import quantile

UNIT = "ms"


def mean_gaps_s(records):
    return [
        (q["token_times"][-1] - q["token_times"][0])
        / (len(q["token_times"]) - 1)
        for q in records["requests"]
        if q["outcome"] in ("ok", "cut") and len(q["token_times"]) > 1
    ]


def read(run):
    r = run.records
    if r["kind"] != "serve":
        return None
    gaps = mean_gaps_s(r)
    return 1e3 * quantile(gaps, 0.5) if gaps else None
