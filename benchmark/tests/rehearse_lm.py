"""`rehearse.py` for the cells of `"kind": "train_lm"`: the cell end to
end OFF the chip at a tiny size (every mechanism kept: a 3 + 1 period,
16 experts top-4 with 4 held, partial rotary, 2 value heads a key head).

    JAX_PLATFORMS=cpu python -m benchmark.tests.rehearse_lm \\
        --workload qwen3next-train-8k-ep16share --trace 1

What it prints is not a result: every number in it is about XLA's CPU
backend, and the line says so (`rehearse.py` holds GPT-2's stand-in and
cannot be edited by the PR that added this file).
"""
from __future__ import annotations

import argparse
import json
import sys

TINY_CONFIG = {
    "vocab_size": 96, "hidden_size": 32, "num_hidden_layers": 4,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "linear_key_head_dim": 8, "linear_num_key_heads": 2,
    "linear_num_value_heads": 4, "linear_value_head_dim": 8,
    "num_experts": 4, "num_experts_routed": 16, "first_expert": 4,
    "num_experts_per_tok": 4, "moe_intermediate_size": 16,
    "shared_expert_intermediate_size": 16,
}
TINY_TRAFFIC = {"global_batch": 2, "seq_len": 128, "report_period": 2,
                "trace_seconds": 1}


def rehearse(workload: str, seed: int = 1, seconds: float = 3.0,
             trace: bool = False):
    from benchmark import run as run_mod

    return run_mod.run_cell(
        workload, seed, seconds, trace,
        rehearsal={"config": TINY_CONFIG, "traffic": TINY_TRAFFIC,
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
