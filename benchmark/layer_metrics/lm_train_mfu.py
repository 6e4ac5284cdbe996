"""Model FLOP/s utilization of a `train_lm` cell: the operations forward
and backward need per token (`lm_flops_bytes.train_flops_per_token`: 6 a
matmul weight, the routed experts by the rows the program's counter says
were routed, attention and the delta rule by shape, nothing recomputed)
times tokens per second, over chips times the bf16 peak."""
from benchmark import lm_flops_bytes

LAYER = "model step"
UNIT = "%"
SOURCE = "host_clock"


def read(run):
    r = run.records
    rows = r.get("counters", {}).get("moe_held_rows_per_token")
    if r["kind"] != "train" or r["steps"] <= 0 or rows is None:
        return None
    tokens_per_s = r["steps"] * r["tokens_per_step"] / r["wall_s"]
    per_token = lm_flops_bytes.train_flops_per_token(
        run.config, int(run.traffic["seq_len"]), rows)
    return 100.0 * per_token * tokens_per_s / (
        run.chips * run.peaks["flops_bf16"])
