"""The benchmark's command: one run of one cell, on the chips it is started on.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Needs a TPU and as many chips as the cell asks for; without them it prints no
result and exits with code 2. There is no CPU mode behind this command (the
tests rehearse the harness through ``benchmark.harness.main`` directly).
"""

import time

STARTED = time.perf_counter()  # set-up is counted from here

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], root=ROOT, platform="tpu", started=STARTED))
