"""Ask/tell SearchStrategy protocol: seed-equivalence of the protocol
drive against the legacy blocking pipeline, state/restore resumability
(strategy-, campaign- and service-level, incl. the process eval
backend), BO end-to-end, and custom-strategy registration."""

import json
import os
import threading
import time

import numpy as np
import pytest

from repro.accel import MCMAccelerator
from repro.core.acl.library import default_library
from repro.core.dse import (
    DSEConfig,
    _objective_matrix,
    default_labeler,
    label_unique,
    random_search,
    run_dse,
)
from repro.core.nsga2 import NSGA2Config, NSGA2Result, nsga2
from repro.core.pareto import non_dominated_mask
from repro.core.strategies import (
    BOStrategy,
    Campaign,
    NSGA2Strategy,
    RandomStrategy,
    SearchStrategy,
    available_strategies,
    drive,
    make_strategy,
    register_strategy,
)
from repro.service import CampaignManager, CampaignSpec, JsonlLabelStore

LIB = default_library()

SMALL = dict(n_train=10, n_qor_samples=2, pop_size=8, n_parents=4,
             n_generations=2)

# deterministic labels only: synth_time/sim_time are wall-clock
TIME_KEYS = ("synth_time", "sim_time")


def small_cfg(seed=0, **kw):
    return DSEConfig(
        n_train=SMALL["n_train"], n_qor_samples=SMALL["n_qor_samples"],
        nsga=NSGA2Config(pop_size=SMALL["pop_size"],
                         n_parents=SMALL["n_parents"],
                         n_generations=SMALL["n_generations"], seed=seed),
        seed=seed, **kw,
    )


def _zdt1_like(genomes):
    x = genomes.astype(np.float64)
    f1 = x[:, 0] / 31.0
    g = 1.0 + 9.0 * x[:, 1:].mean(axis=1) / 31.0
    f2 = g * (1.0 - np.sqrt(f1 / g))
    return np.stack([f1, f2], axis=1)


def _drive_strategy(strat, evaluate, n_obj=2):
    while not strat.done:
        g = strat.ask()
        obj = evaluate(g) if len(g) else np.zeros((0, n_obj))
        strat.tell(g, obj)
    return strat.result()


def _legacy_run_dse(accel, cfg):
    """The seed repo's blocking three-stage pipeline, reproduced from
    public pieces — the equivalence anchor for the protocol drive."""
    from repro.core.features.pipelines import build_extractor
    from repro.core.surrogates import make

    rng = np.random.default_rng(cfg.seed)
    sizes = accel.gene_sizes(LIB, rank_genes=cfg.rank_genes)
    labeler = default_labeler(accel, LIB, rank_genes=cfg.rank_genes,
                              n_qor_samples=cfg.n_qor_samples)
    train = rng.integers(0, sizes[None, :],
                         size=(cfg.n_train, len(sizes)))
    train[0] = accel.exact_genome(LIB, rank_genes=cfg.rank_genes)
    tl = label_unique(labeler, train)
    ext = build_extractor(cfg.pipeline, accel, LIB,
                          rank_genes=cfg.rank_genes)
    X = ext(train)
    models = {}
    for obj in cfg.objectives:
        name = cfg.qor_model if obj == "qor" else cfg.hw_model
        models[obj] = make(name, seed=cfg.seed).fit(X, tl[obj])

    def evaluate(g):
        Xg = ext(g)
        return _objective_matrix(
            {o: models[o].predict(Xg) for o in cfg.objectives},
            cfg.objectives)

    init = train[: cfg.nsga.pop_size].copy()
    if cfg.warm_start and len(init) >= 4:
        from repro.accel.approxfpgas import circuit_level_front

        half = len(init) // 2
        choices = [
            [LIB.index(s.kind, c.name)
             for c in circuit_level_front(LIB, s.kind)]
            for s in accel.slots
        ]
        for t in range(half):
            for j, ch in enumerate(choices):
                init[t, j] = ch[rng.integers(0, len(ch))]
    search = nsga2(sizes, evaluate, cfg.nsga, init=init)
    fl = label_unique(labeler, search.genomes)
    allg = np.concatenate([search.genomes, train])
    all_labels = {k: np.concatenate([fl[k], tl[k]]) for k in fl}
    true_obj = _objective_matrix(all_labels, cfg.objectives)
    return allg, true_obj, non_dominated_mask(true_obj), search


@pytest.fixture(scope="module")
def mcm():
    return MCMAccelerator(1)


# ---------------------------------------------------------------------------
# protocol <-> legacy equivalence
# ---------------------------------------------------------------------------

def test_nsga2_strategy_seed_identical_to_loop():
    """Driving NSGA2Strategy by hand reproduces nsga2() exactly —
    genomes, objectives, history and the dedup'd evaluation count."""
    cfg = NSGA2Config(pop_size=24, n_parents=10, n_generations=6, seed=3)
    ref = nsga2([6] * 4, _zdt1_like, cfg)
    res = _drive_strategy(NSGA2Strategy([6] * 4, cfg), _zdt1_like)
    assert np.array_equal(ref.genomes, res.genomes)
    assert np.array_equal(ref.objectives, res.objectives)
    assert ref.n_evaluated == res.n_evaluated
    assert len(ref.history) == len(res.history)
    for a, b in zip(ref.history, res.history):
        assert np.array_equal(a.genomes, b.genomes)
        assert np.array_equal(a.objectives, b.objectives)
        assert a.n_evaluated == b.n_evaluated


def test_ask_is_idempotent_and_tell_validates():
    cfg = NSGA2Config(pop_size=8, n_parents=4, n_generations=2, seed=0)
    s = NSGA2Strategy([5] * 3, cfg)
    a1, a2 = s.ask(), s.ask()
    assert np.array_equal(a1, a2)      # no RNG consumed by the re-ask
    with pytest.raises(ValueError):
        s.tell(a1[:-1], _zdt1_like(a1[:-1]))
    s.tell(a1, _zdt1_like(a1))
    with pytest.raises(RuntimeError):
        s.tell(a1, _zdt1_like(a1))     # tell without ask


def test_campaign_protocol_matches_run_dse(mcm):
    """The manually stepped Campaign == run_dse byte-for-byte (and both
    == the seed repo's blocking pipeline, reproduced inline)."""
    cfg = small_cfg()
    ref = run_dse(mcm, LIB, cfg)

    campaign = Campaign(mcm, LIB, cfg)
    labeler = default_labeler(mcm, LIB, n_qor_samples=cfg.n_qor_samples)
    requests = []
    while not campaign.done:
        req = campaign.step()
        if req is not None:
            requests.append(req.stage)
            campaign.deliver(req, labeler(req.genomes))
    res = campaign.result()
    assert requests == ["train", "final"]  # EXPLORE never needs labels

    assert np.array_equal(ref.train_genomes, res.train_genomes)
    assert ref.val_pcc == res.val_pcc
    assert np.array_equal(ref.search.genomes, res.search.genomes)
    assert np.array_equal(ref.search.objectives, res.search.objectives)
    assert ref.search.n_evaluated == res.search.n_evaluated
    assert np.array_equal(ref.est_objectives, res.est_objectives)
    assert np.array_equal(ref.true_objectives, res.true_objectives)
    assert np.array_equal(ref.front_mask, res.front_mask)
    assert set(res.timings) == {"label", "train", "explore", "final_eval"}

    legacy_g, legacy_obj, legacy_mask, legacy_search = _legacy_run_dse(
        mcm, cfg)
    assert np.array_equal(res.search.genomes, legacy_g)
    assert np.array_equal(res.true_objectives, legacy_obj)
    assert np.array_equal(res.front_mask, legacy_mask)
    assert res.search.n_evaluated == legacy_search.n_evaluated


def test_random_search_seed_identical(mcm):
    """random_search through the ground-truth Campaign == the seed
    behavior: one uniform draw, one unique-labeled batch."""
    g, obj, mask = random_search(mcm, LIB, n=15, seed=3)

    rng = np.random.default_rng(3)
    sizes = mcm.gene_sizes(LIB)
    exp_g = rng.integers(0, sizes[None, :], size=(15, len(sizes)))
    labels = label_unique(default_labeler(mcm, LIB), exp_g)
    exp_obj = _objective_matrix(labels, ("qor", "energy"))
    assert np.array_equal(g, exp_g)
    assert np.array_equal(obj, exp_obj)
    assert np.array_equal(mask, non_dominated_mask(exp_obj))


# ---------------------------------------------------------------------------
# state() / restore()
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("make_strat", [
    lambda: NSGA2Strategy([5] * 3, NSGA2Config(pop_size=16, n_parents=8,
                                               n_generations=8, seed=7)),
    lambda: RandomStrategy([5] * 3, n_total=64, batch_size=16, seed=7),
    lambda: BOStrategy([5] * 3, n_rounds=6, batch_size=8, n_parents=8,
                       seed=7),
])
def test_strategy_state_roundtrips_mid_run(make_strat):
    """Snapshot after round k, restore on a FRESH instance via a JSON
    round-trip, finish both: identical survivors and eval counts."""
    s1 = make_strat()
    for _ in range(3):
        g = s1.ask()
        s1.tell(g, _zdt1_like(g) if len(g) else np.zeros((0, 2)))
    snap = json.loads(json.dumps(s1.state()))
    s2 = make_strat().restore(snap)
    r1 = _drive_strategy(s1, _zdt1_like)
    r2 = _drive_strategy(s2, _zdt1_like)
    assert np.array_equal(r1.genomes, r2.genomes)
    assert np.array_equal(r1.objectives, r2.objectives)
    assert r1.n_evaluated == r2.n_evaluated


def test_campaign_state_roundtrips_mid_explore(mcm):
    """Campaign snapshot mid-EXPLORE -> fresh Campaign -> identical
    DSEResult (surrogates refit deterministically from the snapshotted
    training set)."""
    cfg = small_cfg()
    labeler = default_labeler(mcm, LIB, n_qor_samples=cfg.n_qor_samples)
    ref = run_dse(mcm, LIB, cfg, labeler=labeler)

    c1 = Campaign(mcm, LIB, cfg)
    # TRAIN tick + delivery, then one EXPLORE round
    req = c1.step()
    c1.deliver(req, labeler(req.genomes))
    assert c1.stage == "explore"
    c1.step()
    snap = json.loads(json.dumps(c1.state()))

    c2 = Campaign(mcm, LIB, cfg).restore(snap)
    assert c2.stage == "explore"
    res = drive(c2, labeler)
    assert np.array_equal(ref.search.genomes, res.search.genomes)
    assert np.array_equal(ref.true_objectives, res.true_objectives)
    assert np.array_equal(ref.front_mask, res.front_mask)
    assert ref.search.n_evaluated == res.search.n_evaluated


def test_campaign_refuses_finished_snapshot(mcm):
    cfg = small_cfg()
    labeler = default_labeler(mcm, LIB, n_qor_samples=cfg.n_qor_samples)
    c = Campaign(mcm, LIB, cfg)
    drive(c, labeler)
    with pytest.raises(ValueError, match="finished"):
        Campaign(mcm, LIB, cfg).restore(c.state())


# ---------------------------------------------------------------------------
# service: cooperative stepping, cancel/resume, live progress
# ---------------------------------------------------------------------------

def _wait_for_stage(mgr, cid, stages=("explore", "final"), timeout=120.0):
    t0 = time.time()
    while time.time() - t0 < timeout:
        st = mgr.status(cid)
        if st["state"] in ("done", "failed"):
            return st
        if (st.get("progress") or {}).get("stage") in stages:
            return st
        time.sleep(0.005)
    raise TimeoutError(f"campaign {cid} never reached {stages}")


def test_more_campaigns_than_workers_multiplex():
    """Cooperative stepping: 4 concurrent campaigns over ONE stepper
    thread all finish with seed-identical fronts."""
    spec = CampaignSpec(accel="mcm2", **SMALL)
    ref = run_dse(MCMAccelerator(1), LIB, spec.dse_config())
    mgr = CampaignManager(eval_workers=2, campaign_workers=1)
    try:
        cids = [mgr.submit(spec) for _ in range(4)]
        for cid in cids:
            assert mgr.wait(cid, timeout=600) == "done"
            assert np.allclose(mgr.result(cid).front_objectives,
                               ref.front_objectives)
    finally:
        mgr.shutdown()


def test_status_reports_live_progress():
    spec = CampaignSpec(accel="mcm2", **{**SMALL, "n_generations": 30})
    mgr = CampaignManager(eval_workers=2, campaign_workers=1)
    try:
        cid = mgr.submit(spec)
        st = _wait_for_stage(mgr, cid, stages=("explore",))
        pr = st.get("progress")
        assert pr is not None
        assert pr["stage"] == "explore"
        assert pr["strategy"] == "nsga2"
        assert "generation" in pr and "labels_requested" in pr
        assert mgr.wait(cid, timeout=600) == "done"
    finally:
        mgr.shutdown()


@pytest.mark.parametrize("eval_backend", ["thread", "process"])
def test_killed_then_resumed_matches_uninterrupted_twin(tmp_path,
                                                        eval_backend):
    """Acceptance: cancel mid-EXPLORE, resume, and the front matches the
    uninterrupted twin (under both eval backends; the process backend is
    the satellite-required configuration)."""
    if eval_backend == "process":
        kw = dict(eval_backend="process", process_workers=1)
    else:
        kw = {}
    spec = CampaignSpec(accel="mcm2",
                        **{**SMALL, "n_generations": 12})
    store = JsonlLabelStore(str(tmp_path / f"labels_{eval_backend}.jsonl"))
    mgr = CampaignManager(store, eval_workers=2, campaign_workers=2,
                          snapshot_path=str(tmp_path / "snaps.jsonl"), **kw)
    try:
        twin = mgr.submit(spec)
        assert mgr.wait(twin, timeout=600) == "done"
        twin_front = mgr.result(twin).front_objectives

        cid = mgr.submit(spec)
        st = _wait_for_stage(mgr, cid)
        if st["state"] != "done":
            mgr.cancel(cid)
        state = mgr.wait(cid, timeout=600)
        if state == "done":        # raced to completion before the cancel
            resumed_front = mgr.result(cid).front_objectives
        else:
            assert state == "cancelled"
            assert cid in mgr.snapshot_ids()
            mgr.resume(cid)
            assert mgr.wait(cid, timeout=600) == "done"
            resumed_front = mgr.result(cid).front_objectives
        assert np.array_equal(resumed_front, twin_front)
    finally:
        mgr.shutdown()
        store.close()


def test_resume_across_manager_restart(tmp_path):
    """A campaign killed WITH its manager resumes on a fresh manager
    from the persisted snapshot file — same id, same front as a clean
    run."""
    snap_path = str(tmp_path / "snaps.jsonl")
    store_path = str(tmp_path / "labels.jsonl")
    spec = CampaignSpec(accel="mcm2", **{**SMALL, "n_generations": 12})
    ref = run_dse(MCMAccelerator(1), LIB, spec.dse_config())

    store = JsonlLabelStore(store_path)
    mgr = CampaignManager(store, eval_workers=2, campaign_workers=1,
                          snapshot_path=snap_path)
    cid = mgr.submit(spec)
    st = _wait_for_stage(mgr, cid)
    if st["state"] != "done":
        mgr.cancel(cid)
    assert mgr.wait(cid, timeout=600) in ("cancelled", "done")
    mgr.shutdown()          # "kill" the process
    store.close()

    store2 = JsonlLabelStore(store_path)
    mgr2 = CampaignManager(store2, eval_workers=2, campaign_workers=1,
                           snapshot_path=snap_path)
    try:
        if cid in mgr2.snapshot_ids():     # not tombstoned by a race
            mgr2.resume(cid)
            assert mgr2.wait(cid, timeout=600) == "done"
            assert np.array_equal(mgr2.result(cid).front_objectives,
                                  ref.front_objectives)
    finally:
        mgr2.shutdown()
        store2.close()


def _free_port():
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _spawn_service(port, store, snaps):
    import subprocess
    import sys

    src = os.path.join(os.path.dirname(__file__), "..", "src")
    return subprocess.Popen(
        [sys.executable, "-m", "repro.service", "--port", str(port),
         "--store", store, "--snapshots", snaps, "--synth-cache", "",
         "--eval-workers", "2", "--campaign-workers", "1"],
        env={**os.environ, "PYTHONPATH": src},
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
    )


def _has_snapshot(path, cid):
    """True once the snapshot log holds a whole record for ``cid``."""
    try:
        with open(path) as f:
            lines = [ln for ln in f if ln.endswith("\n")]
    except FileNotFoundError:
        return False
    return any(json.loads(ln).get("id") == cid for ln in lines)


def _wait_healthy(cli, proc, timeout=60.0):
    t0 = time.time()
    while time.time() - t0 < timeout:
        if proc.poll() is not None:
            raise RuntimeError(f"service died rc={proc.returncode}")
        try:
            if cli.health()["ok"]:
                return
        except Exception:
            time.sleep(0.1)
    raise TimeoutError("service never became healthy")


def test_kill9_service_restart_resume_front_is_byte_identical(tmp_path):
    """Crash-safety acceptance: SIGKILL the service process mid-EXPLORE
    (no atexit, no flush, no snapshot-on-shutdown), restart it on the
    same --store/--snapshots, resume the campaign over HTTP, and the
    finished front is byte-identical to an uninterrupted twin."""
    from repro.service.api import Client

    spec = {"accel": "mcm2", **SMALL, "n_generations": 12}
    ref = run_dse(MCMAccelerator(1), LIB,
                  CampaignSpec(**spec).dse_config())
    store = str(tmp_path / "labels.jsonl")
    snaps = str(tmp_path / "snaps.jsonl")
    port = _free_port()

    proc = _spawn_service(port, store, snaps)
    cli = Client(f"http://127.0.0.1:{port}", timeout=10.0)
    try:
        _wait_healthy(cli, proc)
        cid = cli.submit(**spec)
        t0 = time.time()
        while time.time() - t0 < 120:
            st = cli.status(cid)
            if st["state"] == "done":
                break  # raced to completion before we could kill
            # the stage turns to "explore" a moment before its tick's
            # snapshot is written: kill only once the record is on disk
            if (st.get("progress") or {}).get("stage") in (
                    "explore", "final") and _has_snapshot(snaps, cid):
                break
            time.sleep(0.01)
        if st["state"] == "done":        # raced: nothing left to kill mid-run
            assert np.array_equal(np.asarray(cli.result(cid)["front"]),
                                  ref.front_objectives)
            return
        proc.kill()                      # SIGKILL: no cleanup of any kind
        proc.wait(timeout=30)

        proc = _spawn_service(port, store, snaps)
        _wait_healthy(cli, proc)
        # the tick-boundary snapshot survived the kill
        cli.resume(cid)
        st = cli.wait(cid, timeout=600)
        assert st["state"] == "done"
        assert np.array_equal(np.asarray(cli.result(cid)["front"]),
                              ref.front_objectives)
        # the store the killed process was appending to reopened clean
        h = cli.health()
        assert h["ok"] and h["store"]["writable"]
    finally:
        proc.kill()
        proc.wait(timeout=30)


def test_cancel_validation():
    mgr = CampaignManager(eval_workers=1, campaign_workers=1)
    try:
        spec = CampaignSpec(accel="mcm2", **SMALL)
        cid = mgr.submit(spec)
        assert mgr.wait(cid, timeout=600) == "done"
        with pytest.raises(RuntimeError, match="already done"):
            mgr.cancel(cid)
        with pytest.raises(RuntimeError, match="only cancelled/failed"):
            mgr.resume(cid)
        with pytest.raises(KeyError):
            mgr.resume("nope")
    finally:
        mgr.shutdown()


# ---------------------------------------------------------------------------
# strategy plugging: bo / random / custom, spec + HTTP
# ---------------------------------------------------------------------------

def test_builtin_strategies_registered():
    for name in ("nsga2", "random", "bo"):
        assert name in available_strategies()
    s = make_strategy("bo", [4] * 3, small_cfg())
    assert isinstance(s, BOStrategy)
    with pytest.raises(ValueError, match="unknown strategy"):
        make_strategy("nope", [4] * 3, small_cfg())


def test_bo_campaign_end_to_end_via_service():
    """Acceptance: BOStrategy runs end-to-end through the service
    (POST /campaigns {"strategy": "bo"} equivalent)."""
    mgr = CampaignManager(eval_workers=2, campaign_workers=1)
    try:
        cid = mgr.submit(CampaignSpec(accel="mcm2", strategy="bo", **SMALL))
        assert mgr.wait(cid, timeout=600) == "done"
        res = mgr.result(cid)
        assert res.front_mask.any()
        assert non_dominated_mask(res.front_objectives).all()
        assert mgr.status(cid)["spec"]["strategy"] == "bo"
        with pytest.raises(ValueError, match="unknown strategy"):
            mgr.submit(CampaignSpec(accel="mcm2", strategy="nope", **SMALL))
    finally:
        mgr.shutdown()


def test_http_strategy_and_resume_roundtrip():
    from repro.service.api import Client, make_server

    mgr = CampaignManager(eval_workers=2, campaign_workers=2)
    srv = make_server(mgr, port=0)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        cli = Client(f"http://127.0.0.1:{srv.server_address[1]}")
        assert set(cli.strategies()) >= {"nsga2", "random", "bo"}
        cid = cli.submit(accel="mcm2", strategy="bo", **SMALL)
        st = cli.wait(cid, timeout=600)
        assert st["state"] == "done"
        assert st["spec"]["strategy"] == "bo"
        # cancel/resume route validation on a finished campaign
        import urllib.error

        with pytest.raises(urllib.error.HTTPError) as exc:
            cli.cancel(cid)
        assert exc.value.code == 409
        with pytest.raises(urllib.error.HTTPError) as exc:
            cli.resume("nope")
        assert exc.value.code == 404
    finally:
        srv.shutdown()
        mgr.shutdown()


def test_custom_strategy_in_30_lines(mcm):
    """The STRATEGIES.md pitch: a hill-climber plugged in by name."""

    class HillClimb(SearchStrategy):
        name = "hillclimb"

        def __init__(self, sizes, cfg, *, init=None):
            self.sizes = np.asarray(sizes, dtype=np.int64)
            self.rng = np.random.default_rng(cfg.seed)
            self.rounds = cfg.nsga.n_generations + 1
            self.batch = cfg.nsga.pop_size
            self.round = 0
            self.best = None            # (genome, scalarized objective)
            self.obs = []
            self._pending = None

        @property
        def done(self):
            return self.round >= self.rounds and self._pending is None

        def ask(self):
            if self._pending is None:
                if self.best is None:
                    g = self.rng.integers(0, self.sizes[None, :],
                                          size=(self.batch, len(self.sizes)))
                else:
                    g = np.repeat(self.best[None, :], self.batch, axis=0)
                    mut = self.rng.random(g.shape) < 0.2
                    g = np.where(mut, self.rng.integers(
                        0, self.sizes[None, :], size=g.shape), g)
                self._pending = g
            return self._pending

        def tell(self, genomes, objectives):
            self.obs.append((np.array(genomes), np.array(objectives)))
            score = objectives.sum(axis=1)
            k = int(np.argmin(score))
            self.best = np.array(genomes[k])
            self.round += 1
            self._pending = None

        def result(self):
            G = np.concatenate([g for g, _ in self.obs])
            O = np.concatenate([o for _, o in self.obs])
            return NSGA2Result(genomes=G, objectives=O,
                               front_mask=non_dominated_mask(O),
                               n_evaluated=len(G))

    register_strategy("hillclimb", HillClimb)
    try:
        res = run_dse(mcm, LIB, small_cfg(strategy="hillclimb"))
        assert res.front_mask.any()
    finally:
        available_strategies()  # registry intact
        from repro.core.strategies import STRATEGIES

        STRATEGIES.pop("hillclimb", None)
