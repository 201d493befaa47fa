#!/usr/bin/env python3
"""Benchmark entry point: one measured window of one cell on one TPU host.

    python3 bench/run.py --workload flat-n2000.crowd --seed 7 \\
        --seconds 30 --trace 0

Prints a statistics line, then as its last line of standard output one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics with ``--trace 0``, its per-layer metrics with
``--trace 1``), ``device``, with ``--trace 1`` a ``breakdown``, and last
``checks``: each number compared with the reference beside its limit.
The same checks are the last lines of standard error.  Without a TPU, or
with fewer chips than the cell asks for, it exits non-zero and prints no
result.

String hashing orders the swarm's set iterations, so the run re-executes
itself once with ``PYTHONHASHSEED`` fixed from ``--seed``: one seed, one
trajectory.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

_T0 = "BENCH_T0_MONOTONIC"


def parse():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args()


def pin_hash_seed(seed: int, t0: float) -> None:
    """Re-execute this process with PYTHONHASHSEED fixed by the seed,
    passing on when the first process started."""
    want = str(seed % (1 << 32))
    if os.environ.get("PYTHONHASHSEED") == want:
        return
    env = dict(os.environ, PYTHONHASHSEED=want)
    env[_T0] = repr(t0)
    sys.stdout.flush()
    os.execve(sys.executable, [sys.executable] + sys.argv, env)


def main(control: bool = False) -> int:
    t0 = time.monotonic()
    args = parse()
    pin_hash_seed(args.seed, t0)
    t0 = float(os.environ.pop(_T0, t0))
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [os.path.join(root, "src"), root]
    from bench import harness
    try:
        cell = harness.Cell(args.workload)
        result = harness.run(cell, args.seed, args.seconds,
                             bool(args.trace), t_start=t0, control=control)
    except harness.NoChip as e:
        print(f"bench: {e}; nothing measured", file=sys.stderr)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
