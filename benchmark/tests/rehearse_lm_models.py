"""`rehearse_lm.py` for the cells of `"kind": "train_lm_models"`: the
cell end to end OFF the chip at a tiny size, every mechanism of the
configuration's family kept (`TINY` by `model_type`; GLM-4.7-Flash:
latent attention on 4 heads of 12 + 4, a leading dense layer and 2
expert layers, 16 experts top-4 with 4 held, the MTP module).

    JAX_PLATFORMS=cpu python -m benchmark.tests.rehearse_lm_models \\
        --workload glm47flash-train-8k-ep8share --trace 1

What it prints is not a result: every number in it is about XLA's CPU
backend, and the line says so (`rehearse_lm.py` holds Qwen3-Next's
stand-in and cannot be edited by the PR that added this file).
"""
from __future__ import annotations

import argparse
import json
import sys

#: model_type -> the configuration's keys at a tiny size
TINY = {
    "glm4_moe_lite": {
        "vocab_size": 96, "hidden_size": 32, "num_hidden_layers": 3,
        "num_attention_heads": 4, "num_key_value_heads": 4,
        "q_lora_rank": 16, "kv_lora_rank": 12, "qk_nope_head_dim": 12,
        "qk_rope_head_dim": 4, "v_head_dim": 16, "intermediate_size": 48,
        "moe_intermediate_size": 16, "n_routed_experts": 4,
        "num_experts_routed": 16, "first_expert": 4,
    },
}
TINY_TRAFFIC = {"global_batch": 2, "seq_len": 128, "report_period": 2,
                "trace_seconds": 1}


def rehearse(workload: str, seed: int = 1, seconds: float = 3.0,
             trace: bool = False):
    from benchmark import run as run_mod

    tiny = TINY[run_mod.Cell(workload).config["model_type"]]
    return run_mod.run_cell(
        workload, seed, seconds, trace,
        rehearsal={"config": tiny, "traffic": TINY_TRAFFIC,
                   "peaks_of": "TPU v5 lite",
                   "device_lines": "^tf_XLAPjRtCpuClient"})


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=1)
    parser.add_argument("--seconds", type=float, default=3.0)
    parser.add_argument("--trace", type=int, default=0)
    args = parser.parse_args()
    result = rehearse(args.workload, args.seed, args.seconds,
                      bool(args.trace))
    print("REHEARSAL on", result["device"]["platform"],
          "(not a result):", json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
