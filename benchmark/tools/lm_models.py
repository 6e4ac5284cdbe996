"""`tools/size_train_lm.py` and `tools/lm_control.py` for the cells of
`"kind": "train_lm_models"`: the named tool's own `main`, run while
`train_lm`'s functions read `drivers/lm_models.json`
(`drivers/train_lm_models.py::as_train_lm`).

    JAX_PLATFORMS=cpu python -m benchmark.tools.lm_models size_train_lm \\
        --workload glm47flash-train-8k-ep8share --try global_batch=1,2
    python -m benchmark.tools.lm_models lm_control \\
        --workload glm47flash-train-8k-ep8share --seeds 11,3000000001
"""
from __future__ import annotations

import importlib
import sys

TOOLS = ("size_train_lm", "lm_control")


def main() -> int:
    if len(sys.argv) < 2 or sys.argv[1] not in TOOLS:
        raise SystemExit(f"usage: lm_models {{{'|'.join(TOOLS)}}} ...")
    tool = importlib.import_module("benchmark.tools." + sys.argv[1])
    from benchmark.drivers import train_lm_models

    sys.argv = [sys.argv[1], *sys.argv[2:]]
    with train_lm_models.as_train_lm():
        return tool.main()


if __name__ == "__main__":
    raise SystemExit(main())
