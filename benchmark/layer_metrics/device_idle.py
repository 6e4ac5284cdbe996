"""Share of the traced window in which no operation ran on the device:
1 - union of the device's operation intervals over the window (mean over
the devices used)."""
LAYER = "device"
UNIT = "%"
SOURCE = "device_trace"


def read(run):
    if run.trace is None:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])
