"""Wall time of the window's report windows over their steps, at the
boundary sync."""
LAYER = "model step"
UNIT = "ms"
SOURCE = "host_clock"


def read(run):
    r = run.records
    if r["kind"] != "train" or r["steps"] <= 0:
        return None
    return 1e3 * r["wall_s"] / r["steps"]
