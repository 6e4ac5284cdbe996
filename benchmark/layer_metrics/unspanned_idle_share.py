"""What the trainer's tracing leaves unnamed: the share of the device's
idle time in the traced window that lies inside no `dtpu.trainer.*`
host span (`benchmark/trainer_spans.py::unspanned`). `None` where the
trace holds no such span."""
from benchmark import trainer_spans

LAYER = "trainer"
UNIT = "%"
SOURCE = "program_span"


def read(run):
    return trainer_spans.unspanned_share(run)
