"""The check's control: the program in the nearest precision below float32.

    python3 bench/control.py --workload <name> --seconds <s> --seed <n> [--seed <n> ...]

Every configuration states float32.  The nearest precision below is
bfloat16, and the program has a bfloat16 path of its own: the exchange's
``wire_dtype``, which rounds every value that crosses a partition to
bfloat16 on the wire (``hybrid_policy(wire_dtype=jnp.bfloat16)``).  With
that path switched on, the program is the control: the check has to find
it not correct.

For each seed, in one process (a graph is built once per seed), this runs
a window of the program as a run does and a window of the control on the
same graph, compares both with the plain reference, and prints one JSON
line of readings per seed: the program's readings set the lower end of
each limit, the control's the upper.  A script run by hand on the chip;
the benchmark's own runs never run it.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(REPO), str(REPO / "src")]


def control_runner(graph, prog, vdata):
    """One job through the program's bfloat16 exchange path."""
    import jax.numpy as jnp
    from repro.exec.driver import run_engine
    from repro.exec.policy import hybrid_policy

    policy = hybrid_policy(wire_dtype=jnp.bfloat16)
    return run_engine(graph, prog, policy, vdata, device_loop=True).es


def readings(wl, seed: int, seconds: float, devices) -> dict:
    """Program and control readings of one seed on one graph."""
    import jax
    from bench import harness

    prep = harness.prepare(wl, seed, devices)
    out = {"seed": seed}
    for side, runner in (("program", harness.run_hybrid),
                         ("control", control_runner)):
        with jax.default_device(devices[0]):
            jax.block_until_ready(runner(
                prep.graph, prep.prog,
                prep.place.replicated(prep.kind.vdata(-1))).state)
            wall, done = harness.window(prep.graph, prep.kind, prep.prog,
                                        seconds, runner=runner,
                                        place=prep.place)
        verdict = harness.check(prep, done)
        out[side] = {"jobs": len(done), "job_s": wall / len(done),
                     "failed": verdict["failed"], **verdict["worst"]}
        del done
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, action="append", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    from bench import harness
    wl = harness.load_workload(args.workload)
    try:
        devices = harness.chips(wl.chips)
    except harness.NoChip as e:
        print(f"no chip: {e}", file=sys.stderr)
        return 2
    harness.use_compile_cache()
    for seed in args.seed:
        print(json.dumps(readings(wl, seed, args.seconds, devices)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
