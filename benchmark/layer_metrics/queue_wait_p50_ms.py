"""Median wait between `engine.submit` and admission into the batch,
from the program's own `Request` stamps (`t_admit - t_submit`)."""
from benchmark.loadgen import quantile

LAYER = "engine"
UNIT = "ms"
SOURCE = "program_span"


def read(run):
    r = run.records
    if r["kind"] != "serve":
        return None
    waits = [q["queue_wait_s"] for q in r["requests"]
             if q["queue_wait_s"] is not None]
    return 1e3 * quantile(waits, 0.5) if waits else None
