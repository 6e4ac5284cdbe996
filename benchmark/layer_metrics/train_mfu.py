"""Model FLOP/s utilization: the operations forward and backward need
per token (`flops_bytes.train_flops_per_token`, nothing recomputed
counted) times tokens per second, over chips times the bf16 peak."""
from benchmark import flops_bytes

LAYER = "model step"
UNIT = "%"
SOURCE = "host_clock"


def read(run):
    r = run.records
    if r["kind"] != "train" or r["steps"] <= 0:
        return None
    tokens_per_s = r["steps"] * r["tokens_per_step"] / r["wall_s"]
    per_token = flops_bytes.train_flops_per_token(
        run.config, int(run.traffic["seq_len"]))
    return 100.0 * per_token * tokens_per_s / (
        run.chips * run.peaks["flops_bf16"])
