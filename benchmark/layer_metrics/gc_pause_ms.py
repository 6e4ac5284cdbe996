"""Python's collector a report window: the mean of `gc_s`, the seconds
of collections the trainer's `gc.callbacks` hook timed on
`Timeline.pc` in a window, over the window's `profiling` reports.
`None` where no report carries the key (a program from before it)."""
from benchmark import trainer_spans

LAYER = "trainer"
UNIT = "ms"
SOURCE = "program_counter"
KEY = "gc_s"


def read(run):
    return trainer_spans.report_mean_ms(run, KEY)
