"""Tokens of the whole report windows that closed inside the measured
window over their wall time; boundaries where the context received the
trainer's reports (right after the boundary sync)."""
UNIT = "tokens/s"


def read(run):
    r = run.records
    if r["kind"] != "train" or r["steps"] <= 0:
        return None
    return r["steps"] * r["tokens_per_step"] / r["wall_s"]
