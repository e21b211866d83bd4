"""Multi-host labeling fleet quickstart.

    PYTHONPATH=src python examples/fleet_quickstart.py

One machine's process pool is the labeling ceiling; the fleet tier
splits ground-truth labeling across hosts.  This demo runs the whole
topology locally: an in-process CampaignManager with
``eval_backend="fleet"`` exposes an orchestrator HTTP endpoint, and two
real ``python -m repro.fleet.worker`` subprocesses join it — the second
one ELASTICALLY, after the campaign is already running.  Watch the
stats: every label is computed remotely, the late worker picks up
leases mid-campaign, and when both workers leave, a second campaign
degrades transparently to the in-process backend (``fleet_fallbacks``).

Set REPRO_SMOKE=1 for the CI-sized fast mode."""

import dataclasses
import os
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro.service import CampaignManager, CampaignSpec, JsonlLabelStore

SMOKE = bool(os.environ.get("REPRO_SMOKE"))
SRC = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "src"))


def spawn_worker(base, wid):
    """A real fleet worker process, as `python -m repro.fleet.worker`."""
    return subprocess.Popen(
        [sys.executable, "-m", "repro.fleet.worker",
         "--orchestrator", base, "--id", wid, "--no-warm",
         "--max-idle-s", "300"],
        env={**os.environ, "PYTHONPATH": SRC},
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)


def wait_for(pred, timeout, what):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pred():
            return
        time.sleep(0.1)
    raise TimeoutError(f"timed out waiting for {what}")


def main():
    from repro.service.workers import require_cpu_backend

    require_cpu_backend(
        "examples/fleet_quickstart.py", "run it with JAX_PLATFORMS=cpu")
    from repro.fleet import serve_fleet

    store_path = os.path.join(tempfile.mkdtemp(prefix="fleet_demo_"),
                              "labels.jsonl")
    spec = CampaignSpec(accel="mcm2",
                        n_train=10 if SMOKE else 24, n_qor_samples=2,
                        pop_size=8 if SMOKE else 12,
                        n_parents=4 if SMOKE else 6,
                        n_generations=2 if SMOKE else 3)

    store = JsonlLabelStore(store_path)
    mgr = CampaignManager(store, eval_workers=2, eval_backend="fleet",
                          lease_ttl_s=30.0, heartbeat_ttl_s=6.0)
    fleet = mgr.scheduler.fleet
    srv = serve_fleet(fleet, port=0)
    base = f"http://127.0.0.1:{srv.server_address[1]}"
    print(f"orchestrator: {base}  (join with: python -m repro.fleet.worker "
          f"--orchestrator {base})")

    workers = {}
    try:
        print("\n-- worker w0 joins, campaign starts --")
        workers["w0"] = spawn_worker(base, "w0")
        wait_for(lambda: fleet.stats()["live"] >= 1, 120, "w0 to register")
        c1 = mgr.submit(spec)

        # w1 joins ELASTICALLY: the campaign is already labeling
        wait_for(lambda: fleet.stats()["batches"] >= 1, 120, "first batch")
        print("-- worker w1 joins mid-campaign --")
        workers["w1"] = spawn_worker(base, "w1")
        mgr.wait(c1)

        s = fleet.stats()
        print(f"remote labels={s['remote_labels']}  "
              f"local={s['local_labels']}  batches={s['batches']}  "
              f"chunks={s['chunks']}  requeues={s['requeues']}")
        for wid, w in s["workers"].items():
            print(f"  {wid}: labels={w['labels']}  "
                  f"{w['labels_per_sec']:.2f} labels/s  "
                  f"alive={w['alive']}")

        print("\n-- both workers leave; next campaign degrades in-process --")
        for p in workers.values():
            p.terminate()
        wait_for(lambda: fleet.stats()["live"] == 0, 60, "workers to leave")
        spec2 = dataclasses.replace(spec, seed=7)
        c2 = mgr.submit(spec2)
        mgr.wait(c2)
        ss = mgr.scheduler.stats()
        print(f"fleet batches={ss['fleet_batches']}  "
              f"in-process fallbacks={ss['fleet_fallbacks']}")

        front = mgr.result(c1).front_objectives
        print(f"\ntrue Pareto front ({len(front)} designs, "
              f"PSNR dB vs energy J):")
        for i in np.argsort(front[:, 0])[:8]:
            print(f"  psnr={-front[i, 0]:7.2f}  energy={front[i, 1]:.3e}")
    finally:
        for p in workers.values():
            if p.poll() is None:
                p.kill()
        mgr.shutdown()
        srv.shutdown()
        store.close()


if __name__ == "__main__":
    main()
