"""95th percentile, over the requests due inside the window, of first
token minus the time the request was due. A request that was shed or
failed never got a first token: it enters with the time until the run
stopped waiting for it (a lower bound of its wait, and a large number),
and is counted in `failed`."""
from benchmark.loadgen import quantile

UNIT = "ms"


def ttfts_s(records):
    out = []
    for q in records["requests"]:
        if q["token_times"]:
            out.append(q["token_times"][0] - q["due"])
        else:
            out.append(records["t_stopped_waiting"] - q["due"])
    return out


def read(run):
    r = run.records
    if r["kind"] != "serve" or not r["requests"]:
        return None
    return 1e3 * quantile(ttfts_s(r), 0.95)
