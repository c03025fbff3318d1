#!/usr/bin/env python3
"""Run one benchmark cell; see ``bench/harness.py``.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>
"""
import time

STARTED = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

if __name__ == "__main__":
    from bench.harness import main
    sys.exit(main(started=STARTED))
