"""Fault-tolerant fleet scheduling (ISSUE 5).

Covers the acceptance surface: with retry/timeout machinery ENABLED but
zero injected failures, the fleet at one worker / ``in_flight=1`` replays
the frozen sequential driver bit-for-bit for every registered searcher;
under deterministic fault injection on ``VirtualWorkerPool`` (targeted
test failures, lane kills, stragglers) failed tests are retried on other
lanes with bounded attempts, twice-failing configs are marked known-bad,
abandoned worker-seconds are charged into ``busy``; the gain-priority
scheduler parks jobs already inside the well-performing band and unparks
them when a freshly published model shows more remaining gain; elastic
``in_flight`` stays within its bounds; the subprocess pool drains buffered
results before surfacing lane/fleet death as data; the store supersedes
model artifacts by revision on merge and GCs with ``prune``.
"""
import numpy as np
import pytest

from repro.core import SPECS, ReplayEvaluator, record_space, train_model
from repro.core.account import EvalAccount, Observation
from repro.core.evaluate import ElasticInFlight, VirtualAsyncEvaluator
from repro.core.searcher import (SEARCHERS, make_searcher, run_search,
                                 sequential_run_search)
from repro.fleet import (FAIL_LANE, FAIL_POOL, FAIL_TEST, FailedResult,
                         FleetTuner, TuningJob, VirtualWorkerPool, WorkItem,
                         job_from_registry)
from repro.serve.autotune import (ServeWorkloadStats, serve_space,
                                  serve_workload_fn)
from repro.tuning import ConfigStore

HW = SPECS["tpu_v5e"]
STATS = ServeWorkloadStats()


@pytest.fixture(scope="module")
def gemm():
    from repro.kernels.registry import BENCHMARKS

    bm = BENCHMARKS["matmul"]
    sp = bm.make_space()
    return record_space(sp, lambda c: bm.workload_fn(c, bm.inputs["128"]),
                        HW)


class RecordingPool(VirtualWorkerPool):
    """Virtual pool that records every submitted WorkItem and the peak
    number of concurrently outstanding tests."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.items = []
        self.max_out = 0

    def submit(self, item):
        self.items.append(item)
        super().submit(item)
        self.max_out = max(self.max_out, self.outstanding())


# =============================================================================
# Golden: retry machinery enabled, zero failures => bit-identical traces
# =============================================================================
@pytest.mark.parametrize("name", sorted(SEARCHERS))
def test_retry_enabled_zero_failures_bit_identical(name, gemm):
    """Failure handling must cost nothing when nothing fails: the fleet at
    1 worker / in_flight=1 with retries+straggler policy on replays the
    frozen sequential driver bit-for-bit, for every registered searcher."""
    model = train_model(gemm, kind="exact")
    space = gemm.space
    store = ConfigStore()
    store.save_model(space.name, "128", "tpu_v5e", model, space)
    job = job_from_registry("matmul", "128", "tpu_v5e", budget=40, seed=3,
                            searcher=name)
    rep = FleetTuner([job], VirtualWorkerPool(workers=1), store=store,
                     in_flight=1, publish_models=False,
                     retries=2, straggler_factor=50.0).run()
    s = make_searcher(name, space, seed=3, model=model, cores=HW.cores)
    ev = ReplayEvaluator(gemm)
    sequential_run_search(s, ev, 40)
    r = rep.results[0]
    assert r.trace == ev.trace                 # bit-identical, full trace
    assert r.history == ev.history()
    assert r.failures == 0 and r.abandoned_s == 0.0
    assert r.known_bad == [] and not r.parked


# =============================================================================
# Retry / known-bad on deterministic fault injection
# =============================================================================
def test_failed_test_retries_on_another_lane():
    """First attempt of the first test fails; the retry goes out excluding
    the failed lane and lands, so the job still resolves its full budget
    with every runtime measured — and the wasted attempt is charged."""
    pool = RecordingPool(
        workers=2,
        fail_fn=lambda item: "boom" if item.uid == 0 else None)
    job = job_from_registry("matmul", "128", "tpu_v4", budget=6, seed=0,
                            searcher="random")
    rep = FleetTuner([job], pool, store=None, publish_models=False,
                     retries=2).run()
    r = rep.results[0]
    assert r.trials == 6 and len(r.history) == 6
    assert all(np.isfinite(rt) for _, rt in r.history)
    assert r.failures == 1 and r.known_bad == []
    assert r.abandoned_s > 0.0 and rep.abandoned == r.abandoned_s
    assert r.busy > rep.elapsed * 0  # busy includes the abandoned attempt
    retry = [it for it in pool.items if it.attempt == 1]
    assert len(retry) == 1
    assert retry[0].index == pool.items[0].index
    assert retry[0].exclude == (0,)            # exclude-and-resubmit
    assert rep.max_retries_used == 1


def test_config_failing_twice_is_marked_known_bad():
    """A config whose measurement fails twice stops being retried: it is
    marked known-bad and resolves as an inf row in trace/history, so the
    budget still terminates and nothing is silently dropped."""
    bad = {}

    def fail_fn(item):
        bad.setdefault("index", item.index)
        return "boom" if item.index == bad["index"] else None

    pool = VirtualWorkerPool(workers=2, fail_fn=fail_fn)
    job = job_from_registry("matmul", "128", "tpu_v4", budget=6, seed=0,
                            searcher="random")
    rep = FleetTuner([job], pool, store=None, publish_models=False,
                     retries=2, known_bad_after=2).run()
    r = rep.results[0]
    assert r.known_bad == [bad["index"]]
    assert r.failures == 2                     # original + exactly 1 retry
    assert rep.max_retries_used == 1           # "at most twice" holds
    assert r.trials == 6 and len(r.history) == 6
    inf_rows = [(i, rt) for i, rt in r.history if not np.isfinite(rt)]
    assert inf_rows == [(bad["index"], float("inf"))]
    assert r.best_index is not None and np.isfinite(r.best_runtime)
    assert rep.known_bad == 1


def test_retry_budget_exhaustion_is_not_known_bad():
    """known-bad is reserved for configs whose own measurement failed
    known_bad_after times: exhausting a smaller retry budget on a single
    transient failure resolves the test unmeasured without condemning
    the config."""
    pool = VirtualWorkerPool(
        workers=2,
        fail_fn=lambda item: "boom" if item.uid == 0 else None)
    job = job_from_registry("matmul", "128", "tpu_v4", budget=4, seed=0,
                            searcher="random")
    rep = FleetTuner([job], pool, store=None, publish_models=False,
                     retries=0, known_bad_after=2).run()
    r = rep.results[0]
    assert r.failures == 1 and r.trials == 4
    assert r.known_bad == [] and rep.known_bad == 0
    assert sum(1 for _, rt in r.history if not np.isfinite(rt)) == 1


def test_lane_kill_mid_run_recovers():
    """Kill 1 of 2 lanes mid-run: in-flight tests on it fail as kind
    'lane' (not counted against their configs) and are retried on the
    survivor; every job completes with finite measurements."""
    def jobs():
        return [job_from_registry("matmul", "128", hw, budget=12, seed=1,
                                  searcher="random")
                for hw in ("tpu_v4", "tpu_v5e")]

    base = FleetTuner(jobs(), VirtualWorkerPool(workers=2), store=None,
                      publish_models=False).run()
    pool = VirtualWorkerPool(workers=2,
                             kill_lane_at={1: base.elapsed * 0.3})
    rep = FleetTuner(jobs(), pool, store=None, publish_models=False,
                     retries=2).run()
    for r in rep.results:
        assert r.trials == 12 and len(r.history) == 12
        assert all(np.isfinite(rt) for _, rt in r.history)
        assert r.known_bad == []               # lane faults aren't configs
    assert rep.failures >= 1
    assert pool.alive_workers() == 1


def test_fleet_survives_total_pool_death():
    """Every lane dead: tests resolve as unmeasured (inf) rows instead of
    raising, and the job reports best_index=None with a full trace."""
    pool = VirtualWorkerPool(workers=1, kill_lane_at={0: 0.0})
    job = job_from_registry("matmul", "128", "tpu_v4", budget=4, seed=0,
                            searcher="random")
    rep = FleetTuner([job], pool, store=None, publish_models=False,
                     retries=1).run()
    r = rep.results[0]
    assert r.best_index is None and r.best_runtime == float("inf")
    assert r.best_config == {}
    assert r.trials == 4
    assert all(not np.isfinite(rt) for _, rt in r.history)


def test_straggler_timeout_resubmits_and_charges():
    """A test running way past the job's rolling cost estimate is timed
    out and resubmitted on another lane; its late result is dropped but
    the burned lane-seconds are charged as abandoned work."""
    slow = {}

    def cost_scale(item):
        slow.setdefault("uid", item.uid)
        return 200.0 if item.uid == slow["uid"] else 1.0

    pool = VirtualWorkerPool(workers=2, cost_scale=cost_scale)
    job = job_from_registry("matmul", "128", "tpu_v4", budget=16, seed=2,
                            searcher="random")
    rep = FleetTuner([job], pool, store=None, publish_models=False,
                     retries=2, straggler_factor=3.0).run()
    r = rep.results[0]
    assert rep.timeouts == 1
    assert r.trials == 16 and len(r.history) == 16
    assert all(np.isfinite(rt) for _, rt in r.history)
    # the straggler burned ~200x a normal test on its lane; that cost is
    # real and must appear in busy via record_abandoned
    assert r.abandoned_s > 10 * (r.busy - r.abandoned_s) / 16
    assert r.busy > r.abandoned_s > 0.0


def test_record_abandoned_accounts_busy_not_steps():
    acct = EvalAccount()
    acct.record_completion(1, 1.0, cost=2.0, finished_at=2.0)
    acct.record_abandoned(3.0)
    assert acct.busy == 5.0
    assert acct.abandoned == 3.0 and acct.abandoned_count == 1
    assert acct.steps == 1 and len(acct.trace) == 1
    assert acct.best_index == 1


# =============================================================================
# Gain-priority dispatch: prefer gain, park inside the band, unpark
# =============================================================================
def _serve_job(name, hw, bucket="p4n3", budget=12, seed=5, searcher=None):
    return TuningJob(name=name, space=serve_space(),
                     workload_fn=serve_workload_fn(16, 40, 12, STATS),
                     hardware=hw, bucket=bucket, budget=budget, seed=seed,
                     searcher=searcher)


def _seed_store(store, bucket, hw_key):
    space = serve_space()
    rec = record_space(space, serve_workload_fn(16, 40, 12, STATS),
                       SPECS["tpu_v4"])
    store.save_model(space.name, bucket, hw_key,
                     train_model(rec, kind="exact"), space)


def test_priority_prefers_higher_remaining_gain(monkeypatch):
    """Two model-backed jobs: the one whose prediction says convergence is
    still buying latency gets the lanes; the zero-gain job waits, so the
    high-gain job finishes its budget first."""
    def fake_pred(model, space, hw):
        # job A (tpu_v4): predicted best ~0 => remaining gain ~ its best
        # job B (tpu_v5e): predicted best huge => remaining gain clamps to 0
        val = 1e-9 if hw.name == "tpu_v4" else 1e6
        return np.full(len(space), val)

    monkeypatch.setattr("repro.fleet.tuner.predicted_runtimes", fake_pred)
    store = ConfigStore()
    _seed_store(store, "p4n3", "tpu_v4")
    jobs = [_serve_job("A", "tpu_v4", budget=10, searcher="random"),
            _serve_job("B", "tpu_v5e", budget=10, searcher="random")]
    pool = RecordingPool(workers=2)
    rep = FleetTuner(jobs, pool, store=store, in_flight=2,
                     publish_models=False).run()
    by = rep.by_job()
    assert by["A"].trials == 10 and by["B"].trials == 10
    assert by["A"].elapsed < by["B"].elapsed   # A monopolized the lanes
    assert pool.items[-1].job == "B"           # B's tail ran last


def test_warm_job_inside_band_is_parked(monkeypatch):
    """A warm-started job whose first measurement already sits within
    park_factor of its predicted best stops consuming budget."""
    monkeypatch.setattr("repro.fleet.tuner.predicted_runtimes",
                        lambda m, s, hw: np.full(len(s), 1e6))
    store = ConfigStore()
    _seed_store(store, "p4n3", "tpu_v4")
    job = _serve_job("warm", "tpu_v4", budget=20)
    rep = FleetTuner([job], VirtualWorkerPool(workers=2), store=store,
                     publish_models=False, park_factor=1.1).run()
    r = rep.results[0]
    assert r.warm_started and r.parked
    assert 0 < r.trials < 20                   # budget saved, not spent
    assert rep.parked == 1


def test_parked_job_unparks_on_better_model_publish(monkeypatch):
    """A job parked on a stale artifact's pessimistic prediction resumes
    when a model published later in the run shows more remaining gain."""
    calls = {"v5e": 0}

    def fake_pred(model, space, hw):
        if hw.name == "tpu_v5e":               # job B
            calls["v5e"] += 1
            # stale artifact at _start: pessimistic => B parks instantly;
            # re-priced after A publishes: optimistic => B must unpark
            return np.full(len(space),
                           1e6 if calls["v5e"] == 1 else 1e-9)
        return np.full(len(space), 1e-9)       # job A: never parks

    monkeypatch.setattr("repro.fleet.tuner.predicted_runtimes", fake_pred)
    store = ConfigStore()
    _seed_store(store, "b", "tpu_v5e")         # B's warm-start artifact
    jobs = [_serve_job("A", "tpu_v4", bucket="a", budget=6,
                       searcher="random"),
            _serve_job("B", "tpu_v5e", bucket="b", budget=10)]
    rep = FleetTuner(jobs, VirtualWorkerPool(workers=2), store=store,
                     publish_models=True, park_factor=1.1).run()
    by = rep.by_job()
    assert by["B"].warm_started and by["B"].parked     # it WAS parked...
    assert by["B"].trials == 10                # ...but resumed to budget
    assert calls["v5e"] >= 2                   # re-priced after publish
    # A's completion published the model B re-priced against
    assert store.get_model_dict(serve_space().name, "a", "tpu_v4") \
        is not None


# =============================================================================
# Elastic in_flight
# =============================================================================
def test_elastic_controller_bounds():
    c = ElasticInFlight(lo=2, hi=8)
    assert c.target(4) == 4                    # no samples: lane count
    for _ in range(8):
        c.observe(0.01)
    assert c.target(4) == 4                    # zero variance: no queue
    v = ElasticInFlight(lo=2, hi=8)
    for d in (0.01, 1.0) * 6:
        v.observe(d)
    assert 4 < v.target(4) <= 8                # variance deepens the queue
    assert ElasticInFlight(lo=1, hi=1).target(4) == 1     # clamped
    assert ElasticInFlight(lo=6, hi=9).target(2) == 6     # floor
    with pytest.raises(ValueError):
        ElasticInFlight(lo=0, hi=4)
    with pytest.raises(ValueError):
        ElasticInFlight(lo=4, hi=2)
    c.observe(float("inf"))                    # ignored, no poisoning
    c.observe(-1.0)
    assert c.target(4) == 4


def test_run_search_elastic_respects_budget(gemm):
    ev = VirtualAsyncEvaluator(ReplayEvaluator(gemm), workers=4)
    s = make_searcher("random", gemm.space, seed=2)
    run_search(s, ev, 30, in_flight=2, in_flight_max=6)
    assert ev.steps == 30
    assert ev.outstanding() == 0


def test_run_search_elastic_pinned_matches_sequential(gemm):
    """lo == hi == 1 degenerates to the fixed driver: still golden."""
    s_seq = make_searcher("random", gemm.space, seed=7)
    s_el = make_searcher("random", gemm.space, seed=7)
    ev_seq, ev_el = ReplayEvaluator(gemm), ReplayEvaluator(gemm)
    sequential_run_search(s_seq, ev_seq, 25)
    run_search(s_el, ev_el, 25, in_flight=1, in_flight_max=1)
    assert ev_el.trace == ev_seq.trace


def test_run_search_rejects_bad_elastic_bounds(gemm):
    s = make_searcher("random", gemm.space, seed=0)
    with pytest.raises(ValueError):
        run_search(s, ReplayEvaluator(gemm), 10, in_flight=4,
                   in_flight_max=2)


def test_fleet_elastic_in_flight_stays_within_bounds():
    """High-variance measurement costs grow the fleet's outstanding work
    above the lane count but never past in_flight_max; a fixed window
    never exceeds in_flight."""
    def eval_fn(index, profile):
        cost = 0.5 if index % 2 else 0.001
        return 0.001 * (index + 1), None, cost

    def job():
        return TuningJob(name="j", space=serve_space(), workload_fn=None,
                         hardware="tpu_v4", budget=24, seed=3,
                         searcher="random", eval_fn=eval_fn)

    elastic = RecordingPool(workers=2)
    rep = FleetTuner([job()], elastic, store=None, publish_models=False,
                     in_flight=2, in_flight_max=6).run()
    assert rep.results[0].trials == 24
    assert 2 < elastic.max_out <= 6
    assert rep.in_flight_max == 6
    fixed = RecordingPool(workers=2)
    FleetTuner([job()], fixed, store=None, publish_models=False,
               in_flight=2).run()
    assert fixed.max_out <= 2
    with pytest.raises(ValueError):
        FleetTuner([job()], RecordingPool(workers=2), in_flight=4,
                   in_flight_max=2)


# =============================================================================
# Profile searchers tolerate failed (counter-less) profile tests
# =============================================================================
@pytest.mark.parametrize("name", ["profile", "profile_local"])
def test_profile_searcher_survives_failed_profile(name, gemm):
    model = train_model(gemm, kind="exact")
    s = make_searcher(name, gemm.space, seed=0, model=model,
                      cores=HW.cores)
    first = s.propose(1)
    assert first and first[0].profile
    s.observe([Observation(index=first[0].index, runtime=float("inf"),
                           counters=None)])
    nxt = s.propose(1)                         # re-anchors, doesn't crash
    assert nxt and nxt[0].profile
    assert nxt[0].index != first[0].index


# =============================================================================
# Subprocess pool: lane death surfaces as data, buffered results survive
# =============================================================================
@pytest.mark.slow
def test_subprocess_lane_death_drains_before_fleet_dead():
    """Kill 1 of 2 lanes (then both): completed results are never lost,
    lane death comes back as FailedResult(kind='lane'), and an all-dead
    fleet surfaces as per-item kind='pool' failures instead of raising
    from collect/submit (pre-fix: RuntimeError lost buffered results)."""
    from repro.fleet import SubprocessWorkerPool

    ok = {"kernel": "matmul", "input": "128", "hw": "tpu_v4"}
    pool = SubprocessWorkerPool(workers=2)
    try:
        pool.submit(WorkItem(uid=1, job="j", index=0, payload=dict(ok)))
        res1 = pool.collect(timeout=120)
        assert res1.uid == 1 and res1.error is None
        assert np.isfinite(res1.runtime)
        # crash the lane with a test in flight
        pool.submit(WorkItem(uid=2, job="j", index=1,
                             payload={"sim_crash": True}))
        res2 = pool.collect(timeout=120)
        assert isinstance(res2, FailedResult)
        assert res2.uid == 2 and res2.kind == FAIL_LANE
        # the surviving lane still serves work — no "all dead" raise
        pool.submit(WorkItem(uid=3, job="j", index=2, payload=dict(ok)))
        res3 = pool.collect(timeout=120)
        assert res3.uid == 3 and res3.error is None
        assert res3.runtime == res1.runtime or np.isfinite(res3.runtime)
        # injected per-test failure is kind "test", lane stays alive
        pool.submit(WorkItem(uid=4, job="j", index=3,
                             payload={"sim_fail": True}))
        res4 = pool.collect(timeout=120)
        assert res4.kind == FAIL_TEST and "InjectedFailure" in res4.error
        # kill the survivor: fleet is now dead
        pool.submit(WorkItem(uid=5, job="j", index=4,
                             payload={"sim_crash": True}))
        res5 = pool.collect(timeout=120)
        assert res5.kind == FAIL_LANE
        pool.submit(WorkItem(uid=6, job="j", index=5, payload=dict(ok)))
        res6 = pool.collect(timeout=120)
        assert isinstance(res6, FailedResult) and res6.kind == FAIL_POOL
        assert "died" in res6.error
        assert pool.alive_workers() == 0
    finally:
        pool.close()


# =============================================================================
# Store: artifact revisions supersede on merge; prune GC
# =============================================================================
def test_model_retrain_bumps_revision(gemm):
    model = train_model(gemm, kind="exact")
    store = ConfigStore()
    store.save_model(gemm.space.name, "b", "hw", model, gemm.space,
                     n_obs=10)
    assert store.get_model_dict(gemm.space.name, "b", "hw")["revision"] == 1
    store.save_model(gemm.space.name, "b", "hw", model, gemm.space,
                     n_obs=50)
    art = store.get_model_dict(gemm.space.name, "b", "hw")
    assert art["revision"] == 2 and art["n_obs"] == 50


def test_model_merge_resolves_by_revision(tmp_path, gemm):
    """Pre-fix, a model retrained on more observations tied with its stale
    ancestor (setdefault kept whichever writer saved last-but-loaded-first);
    now the higher revision supersedes on merge."""
    model = train_model(gemm, kind="exact")
    space = gemm.space
    path = str(tmp_path / "s.json")
    a = ConfigStore(path)
    a.save_model(space.name, "b", "hw", model, space, n_obs=10)   # rev 1
    b = ConfigStore(path)                      # loads rev 1
    b.save_model(space.name, "b", "hw", model, space, n_obs=50)   # rev 2
    a.save()          # a still holds rev 1: must adopt rev 2 on merge
    final = ConfigStore(path)
    art = final.get_model_dict(space.name, "b", "hw")
    assert art["revision"] == 2 and art["n_obs"] == 50
    assert a.get_model_dict(space.name, "b", "hw")["revision"] == 2


def test_store_prune_gcs_and_stays_pruned(tmp_path):
    path = str(tmp_path / "s.json")
    store = ConfigStore(path)
    for hw in ("hw1", "hw2"):
        store.put("sp", "b", hw, config={"X": 1}, runtime=1.0, trials=1)
        store.put_model_dict("sp", "b", hw, {"kind": "stub"})
    store.put("other", "b", "hw1", config={"X": 1}, runtime=1.0, trials=1)
    # dry_run reports what WOULD drop without mutating (or saving)
    preview = store.prune(keep_hardware={"hw1"}, dry_run=True)
    assert preview["dropped"] == 2
    assert preview["dropped_entries"] == 1
    assert preview["dropped_models"] == 1
    assert store.get("sp", "b", "hw2") is not None        # untouched
    stats = store.prune(keep_hardware={"hw1"})
    assert stats == preview                               # preview was honest
    assert stats["kept_entries"] == 2 and stats["kept_models"] == 1
    assert store.get("sp", "b", "hw2") is None
    assert store.get_model_dict("sp", "b", "hw2") is None
    assert store.get("sp", "b", "hw1") is not None
    # pruned keys must NOT be resurrected from the on-disk copy
    again = ConfigStore(path)
    assert again.get("sp", "b", "hw2") is None
    assert again.get_model_dict("sp", "b", "hw2") is None
    # field combinations
    assert store.prune(keep_spaces={"sp"})["dropped"] == 1   # drops "other"
    assert store.prune(keep_buckets={"b"})["dropped"] == 0   # nothing left
    assert ConfigStore(path).get("other", "b", "hw1") is None
