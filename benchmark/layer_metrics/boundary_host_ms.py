"""A report boundary on the program's own clock: the mean of
`boundary_s`, the length on `Timeline.pc` of the boundary that opened a
window, over the window's `profiling` reports (every boundary of the
measured window, not only the traced seconds). Read beside
`boundary_idle_ms`, it says how much of the device's wait is host work.
`None` where no report carries the key (a program from before it)."""
from benchmark import trainer_spans

LAYER = "trainer"
UNIT = "ms"
SOURCE = "program_span"
KEY = "boundary_s"


def read(run):
    return trainer_spans.report_mean_ms(run, KEY)
