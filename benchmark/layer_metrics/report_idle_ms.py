"""What one report boundary costs the device: the time inside the
trainer's `dtpu.trainer.report` host spans (`Trainer.fit`'s
`flush_report`: the boundary's `device_get`s, then the metric reports)
in which no operation ran on the device, over the number of such spans
in the traced window (`benchmark/scope_reduce.py`). `None` where the
trace holds no such span (a program from before the spans)."""
from benchmark import scope_reduce

LAYER = "trainer"
UNIT = "ms"
SOURCE = "program_span"
SPAN = "report"


def read(run):
    r = scope_reduce.for_run(run)
    if r is None:
        return None
    name = scope_reduce.names()["span_prefix"] + SPAN
    n = r["span_count"].get(name, 0)
    return 1e3 * r["span_idle_s"][name] / n if n else None
