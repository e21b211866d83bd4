"""Readings that the limits of ``correct`` are set from; not part of any
benchmark run.

    python3 bench/calibrate.py --workload <name> --seeds 11,12,13 --seconds 8

For each seed, a run of the cell as the benchmark makes it, followed by
the control's reading on the same answers (the reference in the next
lower precision put in the program's place).  With ``--one-process``
(LM cells) all seeds go through one set-up: each seed's weights and
prompts through the same compiled programs, one group each.
"""

import argparse
import json
import os
import sys
import time

T_START = time.perf_counter()
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from benchkit import harness  # noqa: E402


def one_process(workload: str, seeds) -> None:
    import jax

    spec = harness.load_spec()
    resolved = harness.resolve(spec, workload)
    if jax.devices()[0].platform == "cpu":
        raise harness.Refused("JAX found no accelerator")
    cache = os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                                  os.path.join(harness.ROOT, ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", cache)
    sys.path.insert(0, os.path.join(harness.ROOT, "src"))
    run = harness.Run(workload, seeds[0], 0, 0, resolved, harness.ROOT)
    driver = harness.driver_for(run.traffic).Driver(run)
    try:
        driver.setup()
        rows = driver.calibrate(seeds)
    finally:
        driver.close()
    print(json.dumps({"workload": workload, "readings": rows}), flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--one-process", action="store_true")
    a = ap.parse_args()
    seeds = [int(s) for s in a.seeds.split(",")]
    if a.one_process:
        return one_process(a.workload, seeds)
    for seed in seeds:
        harness.main(["--workload", a.workload, "--seed", str(seed),
                      "--seconds", str(a.seconds), "--trace", "0"],
                     t_start=T_START, control=True)


if __name__ == "__main__":
    main()
