#!/usr/bin/env python3
"""The benchmark's one command; see ``yardstick/harness.py``.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
"""
import sys
import time

T_PROCESS = time.perf_counter()

if __name__ == "__main__":
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parent))
    from yardstick.harness import main

    sys.exit(main(t_process=T_PROCESS))
