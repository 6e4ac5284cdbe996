"""Share of the traced window in which a collective operation ran on a
device and nothing else did (mean over the devices used)."""
LAYER = "collectives"
UNIT = "%"
SOURCE = "device_trace"


def read(run):
    if run.trace is None or run.chips < 2:
        return None
    return 100.0 * run.trace["collective_exposed_s"] / run.trace["window_s"]
