"""Output tokens that reached their clients inside the window, over the
window's length."""
UNIT = "tokens/s"


def read(run):
    r = run.records
    if r["kind"] != "serve":
        return None
    return r["tokens_in_window"] / (r["t1"] - r["t0"])
