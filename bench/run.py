"""Run one cell of the benchmark once and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  The cell's chips must be TPUs that JAX
can see: without them the run exits non-zero before any work and prints
no result.  The last line of standard output is the result as one JSON
object; the numbers the check compared, each beside its limit, are the
last lines of standard error.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(REPO), str(REPO / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness
    wl = harness.load_workload(args.workload)
    try:
        devices = harness.chips(wl.chips)
    except harness.NoChip as e:
        print(f"no chip: {e}", file=sys.stderr)
        return 2
    harness.use_compile_cache()
    result = harness.run_cell(wl, args.seed, args.seconds, bool(args.trace),
                              devices)
    print(json.dumps(result), flush=True)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
