#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints, as the last line of standard output, one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and,
with ``--trace 1``, ``breakdown``), and last ``checks``: each number
compared with the reference beside its limit. Exits 2 with no result
when JAX finds no TPU, or fewer chips than the cell asks for.
See ``harness.py``.
"""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench.harness import run  # noqa: E402

if __name__ == "__main__":
    sys.exit(run(sys.argv[1:]))
