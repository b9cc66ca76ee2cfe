"""Run one cell once with the control in the program's place. In a query
cell each answer is replaced by the plain reference computed one
precision lower (float32 for the configuration's float64). In an ops cell
each op module's ``control()`` breaks one guarantee the configuration
states (``ops/lifecycle.py``: buckets by the key modulo the bucket count,
in place of the engine's hash). The check has to come out false; the
numbers it prints are the control's readings, the upper ends the limits
in ``traffic/*.json`` are set below.

    python3 benchmark/control.py --workload <cell> --seed <n> --seconds <s>

The benchmark's own runs never run it. Like ``run.py`` it needs the chips
the cell asks for.
"""

from __future__ import annotations

import argparse
import contextlib
import sys

import numpy as np

import run


def lower_precision(query, answer, lake):
    return query.module.reference(lake, query.spec, query.lits, np.float32)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    root = run.prepare()
    import harness
    cell = harness.load_cell(root, args.workload)
    try:
        with contextlib.ExitStack() as stack:
            fault = lower_precision
            if "ops" in cell.traffic:
                fault = None
                for _, mod in harness.plan_ops(cell):
                    stack.enter_context(mod.control())
            result = harness.run_cell(root, args.workload, args.seed,
                                      args.seconds, False, fault=fault)
    except harness.NoAccelerator as e:
        print(f"no result: {e}", file=sys.stderr)
        return 3
    run.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
