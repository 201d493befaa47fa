#!/usr/bin/env python3
"""The control's reading for one seed, at the cell's own size, on the
chip.  Same arguments and output as ``run.py``; the ``checks`` also hold
``control_mismatches``: the volunteers of the window's sampled ticks
whose requests the control (the reference with its rarest-first rotation
tie-break dropped), put in the program's place, decides otherwise than
the reference.  The benchmark's own runs never compute it.

    python3 bench/control.py --workload flat-n2000.crowd --seed 7 \\
        --seconds 10 --trace 0
"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run  # noqa: E402

if __name__ == "__main__":
    sys.exit(run.main(control=True))
