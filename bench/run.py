"""Benchmark entry point: `python3 bench/run.py --workload <cell> --seed
<n> --seconds <s> --trace <0|1>` from the root of a checkout (see
harness.py)."""
import time

T0 = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.run(t0=T0))
