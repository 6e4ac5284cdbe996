"""The chunked gated-delta rule's share of its roofline: the least time
the chip could take for the rule itself in the steps traced
(`lm_flops_bytes.delta_rule_step`: the recurrence's three products a
token and head, forward and backward, once; memory-bound at 128 x 128
a head) over the time under the `gdn_scan` scope, which holds the
forward, the forward repeated in the backward, and the backward. `None`
where the program opens no such scope."""
from benchmark import lm_flops_bytes, lm_scope_reduce

LAYER = "kernels"
UNIT = "%"
SOURCE = "device_trace"


def read(run):
    tokens = int(run.traffic["global_batch"]) * int(run.traffic["seq_len"])
    return lm_scope_reduce.roofline_share(
        run, "gdn_scan",
        lm_flops_bytes.delta_rule_step(run.config, tokens // run.chips))
