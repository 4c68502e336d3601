#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

See ``bench/harness/runner.py`` for what is measured and printed.
"""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.harness import runner  # noqa: E402

T_START = runner.process_start()

if __name__ == "__main__":
    sys.exit(runner.main(sys.argv[1:], ROOT, T_START))
