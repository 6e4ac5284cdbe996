"""What a whole report boundary costs the device: the time inside the
trainer's `dtpu.trainer.boundary` host spans (from the start of
`Trainer.fit`'s `flush_report` at a report boundary until the next
step's dispatch returns: the sync, the metric reports, the control
calls, the op's end, the next batch and its put, the dispatch) in which
no operation ran on the device, over the number of such spans in the
traced window (`benchmark/scope_reduce.py`). It encloses
`report_idle_ms`'s span. `None` where the trace holds no such span (a
program from before the span)."""
from benchmark import trainer_spans

LAYER = "trainer"
UNIT = "ms"
SOURCE = "program_span"
SPAN = "boundary"


def read(run):
    return trainer_spans.span_idle_ms(run, SPAN)
