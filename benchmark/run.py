"""Run one benchmark cell once and print its result as the last line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Run from the root of a checkout, in one process that holds the chips.
With ``--trace 0`` the line carries the cell's end-to-end metrics, taken
with the program's span tracing off; with ``--trace 1`` its per-layer
metrics, from the program's spans and counters and a profiler trace of
the window. The numbers that decide ``correct`` are printed, each beside
its limit, as the last lines of standard error and under ``checks``, the
last key of the line.

Without a TPU, or with fewer chips than the cell asks for, the run fails
(exit 3) and prints no result. JAX's persistent compilation cache is kept
in ``.jax_cache`` at the root of the checkout, so only a checkout's first
run compiles.
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    root = prepare()
    import harness
    try:
        result = harness.run_cell(root, args.workload, args.seed,
                                  args.seconds, bool(args.trace))
    except harness.NoAccelerator as e:
        print(f"no result: {e}", file=sys.stderr)
        return 3
    emit(result)
    return 0


def prepare() -> str:
    """Set up the process before JAX is imported; return the checkout's
    root. The compile cache lives at a fixed path in the checkout (the
    program keeps it there too when the variable is unset), and libtpu
    writes no logs outside it."""
    here = os.path.dirname(os.path.abspath(__file__))
    root = os.path.dirname(here)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(root, ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [here, root]
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return root


def emit(result: dict) -> None:
    """The compared numbers as the last lines of standard error, then the
    result as the last line of standard output."""
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    sys.exit(main())
