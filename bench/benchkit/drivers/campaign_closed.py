"""Closed-loop DSE campaigns over HTTP: ``clients`` callers, each
submitting a campaign with ``Client.submit``, waiting for it, and
submitting the next, against the service as ``python -m repro.service``
builds it, with a fresh label store, synth cache and snapshot file.

Traffic parameters: ``clients``, ``accel``, ``spec`` (CampaignSpec
overrides; empty runs the defaults), ``service_args`` (service command
line), ``synth_structural`` (the service's ``REPRO_SYNTH_STRUCTURAL``
switch: whether synthesis may serve a variant from another variant's
compile of the same deploy structure), ``warm_buckets`` (population
buckets whose fused programs set-up compiles), ``drain_s`` (how long
campaigns still running at the close may take) and ``check_labels`` (how
many labels the reference synthesizes again).  Callers stop sending at
the close of the window; what they sent is drained and counted.  Each
campaign's seed comes from the run's seed, the client and the campaign's
number.  Set-up warms only the fused programs; the label store and the
synth cache start empty, so their work is measured.
"""

from __future__ import annotations

import os
import threading
import time

import numpy as np

from ..refs import gaussian as ref
from ..refs import synth as synth_ref
from ..stats import sample

SYNTH_KEYS = ("flops", "hbm_bytes", "latency", "energy")


def check_library(c: dict, library) -> None:
    """The program's circuit catalogue must be the configuration's: a
    genome then means the same circuits in the program and the reference."""
    for kind, names in c["circuits"].items():
        got = [x.name for x in library.kind(kind)]
        if got != names:
            raise ValueError(f"{kind} circuits differ from the configuration: {got}")


class Driver:
    def __init__(self, run):
        self.run = run
        self.c = run.config
        self.t = run.traffic
        self.attempted = 0
        self.failed = 0
        self.done = []          # campaign ids that finished
        self.t_last = 0.0
        self._lock = threading.Lock()

    def campaign_seed(self, client: int, k: int) -> int:
        return int(np.random.default_rng([self.run.seed, client, k])
                   .integers(0, 2 ** 31 - 1))

    def setup(self):
        from repro.accel import fused
        from repro.core.acl.library import default_library
        from repro.core.features import synth
        from repro.service.__main__ import build_manager, build_parser
        from repro.service.api import Client, make_server
        from repro.service.campaigns import make_accelerator

        wd = self.run.workdir
        args = build_parser().parse_args(list(self.t["service_args"]) + [
            "--port", "0",
            "--store", os.path.join(wd, "labels.jsonl"),
            "--synth-cache", os.path.join(wd, "synth.jsonl"),
            "--snapshots", os.path.join(wd, "snapshots.jsonl"),
        ])
        lib = default_library()
        check_library(self.c, lib)
        self.fused = fused
        self._structural = synth.STRUCTURAL_KEYS
        synth.STRUCTURAL_KEYS = bool(self.t["synth_structural"])
        # the fused programs of every population bucket the scheduler can
        # form, on the campaigns' QoR inputs; also spends the program's
        # own first-call verification against its numpy engine
        accel = make_accelerator(self.t["accel"])
        q = self.c["qor"]
        inputs = accel.sample_inputs(q["samples"], seed=q["seed"])
        rng = np.random.default_rng([self.run.seed, 99])
        widths = [len(lib.kind(s.kind)) for s in accel.slots]
        for b in self.t["warm_buckets"]:
            genomes = np.stack([rng.integers(0, w, b) for w in widths], axis=1)
            accel.qor_batch(genomes, lib, inputs)
        self.manager = build_manager(args)
        self.server = make_server(self.manager, args.host, args.port)
        self.thread = threading.Thread(target=self.server.serve_forever,
                                       daemon=True)
        self.thread.start()
        self.client = Client(f"http://{args.host}:{self.server.server_address[1]}")
        self.base = self._counters()

    def _counters(self) -> dict:
        s = self.manager.scheduler.stats()
        y = self.manager.synth_cache.stats()
        return {"labeled": s["labeled"], "batches": s["batches"],
                "hits": y["identity_hits"] + y["structural_hits"],
                "compiles": y["compiles"], "fused": self.fused.stats()}

    def _client(self, i: int):
        k = 0
        while time.perf_counter() < self.t_end:
            spec = dict(self.t["spec"], accel=self.t["accel"],
                        seed=self.campaign_seed(i, k))
            with self._lock:
                self.attempted += 1
            try:
                cid = self.client.submit(**spec)
                st = self.client.wait(cid, timeout=self.t_end - time.perf_counter()
                                      + self.t["drain_s"], poll=0.05)
                t_done = time.perf_counter()
                with self._lock:
                    self.t_last = max(self.t_last, t_done)
                    if st["state"] == "done":
                        self.done.append(cid)
                    else:
                        self.failed += 1
                        self.run.log(f"campaign {cid} ended {st['state']}: "
                                     f"{st.get('error')}")
            except Exception as exc:  # noqa: BLE001 - a failed campaign is counted
                self.run.log(f"campaign {i}/{k} failed: {exc!r}")
                with self._lock:
                    self.failed += 1
            k += 1

    def window(self, t0: float, t_end: float):
        self.t0, self.t_end = t0, t_end
        self.threads = [threading.Thread(target=self._client, args=(i,))
                        for i in range(self.t["clients"])]
        for th in self.threads:
            th.start()
        time.sleep(max(0.0, t_end - time.perf_counter()))
        self.end = self._counters()
        self.window_s = time.perf_counter() - t0

    def finish(self):
        for th in self.threads:
            th.join()
        self.drained = self._counters()

    def e2e(self) -> dict:
        """Labels of the campaigns sent in the window over the time from
        the window's start until the last of them finished: a whole
        campaign is the unit of work, as a group is for the LM cell, so
        the close of the window never cuts a batch of labels in two."""
        return {"labels_per_s": (self.drained["labeled"] - self.base["labeled"])
                / (self.t_last - self.t0)}

    def layer_inputs(self) -> dict:
        d = {k: self.end[k] - self.base[k]
             for k in ("labeled", "batches", "hits", "compiles")}
        return {"kind": "campaign", "labels": d["labeled"],
                "sched_labeled": d["labeled"], "sched_batches": d["batches"],
                "synth_hits": d["hits"], "synth_compiles": d["compiles"]}

    def release(self):
        self.fronts = {cid: self.client.front(cid) for cid in self.done}
        # every label the finished campaigns were given: genome -> labels
        self.labeled = {}
        for cid in self.done:
            res = self.manager.result(cid)
            for t, g in enumerate(res.search.genomes):
                self.labeled[tuple(int(v) for v in g)] = {
                    k: float(res.final_labels[k][t]) for k in SYNTH_KEYS}
        self.fused_stats = self.fused.stats()
        self.close()

    def check(self) -> list:
        """Every finished campaign's front: each point's QoR label against
        the reference filter on the same images, exactly.  Synthesis:
        every label's energy, and a sample of labels drawn from the seed,
        whole, against the reference's own deploy graphs; the compiles
        the run paid against the fewest that can have labelled what it
        labelled."""
        gap, n = self._gap()
        if n == 0:
            return []
        lim = self.c["limits"]
        return [
            {"name": "qor_gap_db", "value": gap, "limit": lim["qor_gap_db"]},
            *self._synth_checks(),
            {"name": "fused_pins", "value": self.fused_stats["pins"],
             "limit": lim["fused_pins"]},
            {"name": "fused_fallback_calls",
             "value": self.fused_stats["fallback_calls"],
             "limit": lim["fused_fallback_calls"]},
        ]

    def _synth_checks(self) -> list:
        c, lim = self.c, self.c["limits"]

        def rel(got, want):
            return abs(got - want) / abs(want) if want else abs(got)

        energy = [rel(row[f["objectives"].index("energy")],
                      synth_ref.energy(c, synth_ref.classes(c, g)))
                  for f in self.fronts.values()
                  for g, row in zip(f["genomes"], f["front"])]
        energy += [rel(lab["energy"], synth_ref.energy(c, synth_ref.classes(c, g)))
                   for g, lab in self.labeled.items()]
        genomes = sorted(self.labeled)
        rng = np.random.default_rng([self.run.seed, 11])
        gaps = {k: 0.0 for k in SYNTH_KEYS}
        for j in sample(rng, len(genomes), self.t["check_labels"]):
            got, want = self.labeled[genomes[j]], synth_ref.labels(c, genomes[j])
            wrong = {k: (got[k], want[k]) for k in SYNTH_KEYS if got[k] != want[k]}
            if wrong:
                self.run.log(f"synthesis labels of {list(genomes[j][:9])} "
                             f"(program, reference): {wrong}")
            for k in SYNTH_KEYS:
                gaps[k] = max(gaps[k], rel(got[k], want[k]))
        d = {k: self.drained[k] - self.base[k]
             for k in ("labeled", "hits", "compiles")}
        return [
            {"name": "synth_flops_gap", "value": gaps["flops"],
             "limit": lim["synth_flops_gap"]},
            {"name": "synth_bytes_gap", "value": gaps["hbm_bytes"],
             "limit": lim["synth_bytes_gap"]},
            {"name": "latency_gap", "value": gaps["latency"],
             "limit": lim["latency_gap"]},
            {"name": "energy_gap", "value": max([gaps["energy"]] + energy),
             "limit": lim["energy_gap"]},
            {"name": "synth_compiles_short",
             "value": max(0, synth_ref.structures(c, genomes) - d["compiles"]),
             "limit": lim["synth_compiles_short"]},
            {"name": "synth_lookups_over_labels",
             "value": max(0, d["compiles"] + d["hits"] - d["labeled"]),
             "limit": lim["synth_lookups_over_labels"]},
        ]

    def _gap(self, control=False):
        """Widest gap between the served QoR labels (or, for the control,
        the bfloat16 reference's) and the reference's, and their count."""
        q = self.c["qor"]
        images = ref.sample_images(q["samples"], q["image"], q["seed"])
        gap, n = 0.0, 0
        for front in self.fronts.values():
            for genome, row in zip(front["genomes"], front["front"]):
                got = (ref.qor(images, genome, precision="bfloat16") if control
                       else -float(row[front["objectives"].index("qor")]))
                gap = max(gap, abs(got - ref.qor(images, genome)))
                n += 1
        return gap, n

    def control(self) -> dict:
        """The reading of the control, the reference in bfloat16 put in
        the program's place, on the same fronts."""
        return {"qor_gap_db": self._gap(control=True)[0]}

    def close(self):
        server = getattr(self, "server", None)
        if server is not None:
            self.server = None
            server.shutdown()
            server.server_close()
            self.manager.shutdown()
        if hasattr(self, "_structural"):
            from repro.core.features import synth

            synth.STRUCTURAL_KEYS = self._structural
