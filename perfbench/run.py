#!/usr/bin/env python3
"""One run of one cell:

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process that imports JAX itself and starts no child that needs the
chip. Without a TPU (or with fewer chips than the cell asks for) it exits
non-zero and prints no result; ``--rehearse`` drives the same control flow
at a tiny size on the CPU and says that it is no measurement. Everything
the program says goes to stderr; stdout carries progress lines and, last,
the contract's one JSON object.
"""

import os
import sys
import time

T0 = time.monotonic()       # set-up is counted from here
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if __name__ == "__main__":
    from perfbench import harness
    sys.exit(harness.main(T0))
