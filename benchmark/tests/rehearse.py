"""Rehearsal-only entry: a cell end to end OFF the chip at a tiny size.

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python -m benchmark.tests.rehearse --workload xl-train-fsdp4

It runs the same `run_cell` as the command, with a two-layer 64-wide
stand-in for the configuration and a shrunken mix, to find wrong paths,
arguments, meshes and control flow before a chip call is spent. The
command itself (`python3 -m benchmark.run`) has no way to reach this: it
refuses anything but a TPU. What a rehearsal prints is not a result:
every number in it is about XLA's CPU backend, and the line says so.
"""
from __future__ import annotations

import argparse
import json
import sys

TINY_CONFIG = {
    "n_layer": 2, "n_head": 4, "n_embd": 64, "n_positions": 256,
    "n_ctx": 256, "vocab_size": 250, "padded_vocab_size": 256,
    "gpt_config_overrides": {"remat": False},
}
TINY_TRAFFIC = {
    "train": {"global_batch": 8, "seq_len": 128, "report_period": 2,
              "reference_rows_per_call": 4, "trace_seconds": 1},
    "serve": {
        "arrivals": {"rate_per_s": 20.0},
        "clients": 4,
        "prompt_tokens": {"dist": "uniform", "min": 4, "max": 40},
        "output_tokens": {"dist": "uniform", "min": 2, "max": 12},
        "max_total_tokens": 64,
        "preroll_seconds": 1, "drain_seconds": 10, "trace_seconds": 1,
        "serving": {"page_size": 16, "num_pages": 65,
                    "max_pages_per_request": 4, "max_batch_size": 4,
                    "max_new_tokens": 12, "prefill_rows": 2,
                    "prefill_seq": 64},
    },
}


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=1)
    parser.add_argument("--seconds", type=float, default=3.0)
    parser.add_argument("--trace", type=int, default=0)
    args = parser.parse_args()
    from benchmark import run as run_mod

    kind = run_mod.Cell(args.workload).traffic["kind"]
    result = run_mod.run_cell(
        args.workload, args.seed, args.seconds, bool(args.trace),
        rehearsal={"config": TINY_CONFIG, "traffic": TINY_TRAFFIC[kind],
                   "peaks_of": "TPU v5 lite",
                   "device_lines": "^tf_XLAPjRtCpuClient"},
    )
    print("REHEARSAL on", result["device"]["platform"],
          "(not a result):", json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
