"""The benchmark's command.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine that holds the chips the
cell asks for.  Prints one JSON result as the last line of standard
output; exits non-zero, with no result, where JAX finds no accelerator.
"""

import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
# the TPU runtime's own logs would go to a fixed path under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from benchkit.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
