"""Mean wall time of a decode iteration as the engine times it
(`dtpu_serving_decode_iteration_seconds` sum over count: dispatch of the
jitted step until its tokens are on the host)."""
LAYER = "model step"
UNIT = "ms"
SOURCE = "program_counter"


def read(run):
    r = run.records
    if r["kind"] != "serve" or r["counters"]["decode_iter_count"] <= 0:
        return None
    return 1e3 * r["counters"]["decode_iter_seconds"] \
        / r["counters"]["decode_iter_count"]
