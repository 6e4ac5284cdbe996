"""Tokens streamed per decode iteration: the deltas of the program's
`dtpu_serving_tokens_total` over `dtpu_serving_decode_iterations_total`
(batch occupancy as the clients feel it; first tokens come from prefill
and are included)."""
LAYER = "engine"
UNIT = "tokens"
SOURCE = "program_counter"


def read(run):
    r = run.records
    if r["kind"] != "serve" or r["counters"]["decode_iterations"] <= 0:
        return None
    return r["counters"]["tokens"] / r["counters"]["decode_iterations"]
