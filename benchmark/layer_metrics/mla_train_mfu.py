"""Model FLOP/s utilization of a latent-attention cell: the operations
forward and backward need per token (`mla_flops_bytes.train_flops_per_token`:
6 a matmul weight, the routed experts by the rows the program's counter
says were routed, attention by shape at 20 x S x 256, the MTP module's
block, projection and head pass where the configuration has it, nothing
recomputed) times tokens per second, over chips times the bf16 peak.
`None` where the configuration is of another family or the program
reports no such counter."""
from benchmark import mla_flops_bytes

LAYER = "model step"
UNIT = "%"
SOURCE = "host_clock"


def read(run):
    r = run.records
    rows = r.get("counters", {}).get("moe_held_rows_per_token")
    if (r["kind"] != "train" or r["steps"] <= 0 or rows is None
            or "kv_lora_rank" not in run.config):
        return None
    tokens_per_s = r["steps"] * r["tokens_per_step"] / r["wall_s"]
    per_token = mla_flops_bytes.train_flops_per_token(
        run.config, int(run.traffic["seq_len"]), rows)
    return 100.0 * per_token * tokens_per_s / (
        run.chips * run.peaks["flops_bf16"])
