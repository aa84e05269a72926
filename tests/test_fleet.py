"""Async evaluation core + fleet orchestrator.

Covers the ISSUE 4 acceptance surface: the event-driven driver replays the
legacy sequential driver bit-identically at ``in_flight=1`` for every
registered searcher; out-of-order completions are accounted in completion
order; the ``FleetTuner`` shares one store across hardware targets and
warm-starts new arrivals from the nearest artifact in ≤ half the cold
trials; hardware naming drift maps to one store key; the subprocess worker
backend (slow) agrees with the in-process backends.
"""
import numpy as np
import pytest

from repro.core import SPECS, ReplayEvaluator, record_space, train_model
from repro.core.account import Candidate, EvalAccount
from repro.core.evaluate import VirtualAsyncEvaluator
from repro.core.hwspec import (fingerprint, get, hardware_key,
                               normalize_name)
from repro.core.searcher import (SEARCHERS, make_searcher, run_search,
                                 sequential_run_search)
from repro.fleet import (FleetTuner, ThreadWorkerPool, TuningJob,
                         VirtualWorkerPool, job_from_registry)
from repro.serve.autotune import (ServeWorkloadStats, serve_space,
                                  serve_workload_fn)
from repro.tuning import ConfigStore

HW = SPECS["tpu_v5e"]
STATS = ServeWorkloadStats()
BUCKET_SHAPES = {"p1n1": (16, 6), "p8n8": (80, 28), "p4n3": (40, 12)}


@pytest.fixture(scope="module")
def gemm_recorded():
    from repro.kernels.registry import BENCHMARKS

    bm = BENCHMARKS["matmul"]
    sp = bm.make_space()
    return record_space(sp, lambda c: bm.workload_fn(c, bm.default_input), HW)


# =============================================================================
# Golden: in_flight=1 event-driven == legacy sequential, full trace
# =============================================================================
@pytest.mark.parametrize("name", sorted(SEARCHERS))
def test_async_driver_golden_vs_sequential(name, gemm_recorded):
    """Every registered searcher: identical trace, history and account."""
    model = train_model(gemm_recorded, kind="exact")
    ctx = dict(model=model, cores=HW.cores)
    s_seq = make_searcher(name, gemm_recorded.space, seed=3, **ctx)
    s_evt = make_searcher(name, gemm_recorded.space, seed=3, **ctx)
    ev_seq, ev_evt = ReplayEvaluator(gemm_recorded), \
        ReplayEvaluator(gemm_recorded)
    sequential_run_search(s_seq, ev_seq, 40)
    run_search(s_evt, ev_evt, 40, in_flight=1)
    assert ev_evt.trace == ev_seq.trace            # bit-identical, full trace
    assert ev_evt.history() == ev_seq.history()
    assert ev_evt.best_index == ev_seq.best_index
    assert ev_evt.elapsed == ev_seq.elapsed


def test_run_search_rejects_bad_in_flight(gemm_recorded):
    s = make_searcher("random", gemm_recorded.space, seed=0)
    with pytest.raises(ValueError):
        run_search(s, ReplayEvaluator(gemm_recorded), 10, in_flight=0)


def test_run_search_in_flight_respects_budget(gemm_recorded):
    ev = VirtualAsyncEvaluator(ReplayEvaluator(gemm_recorded), workers=4)
    s = make_searcher("random", gemm_recorded.space, seed=2)
    run_search(s, ev, 17, in_flight=4)
    assert ev.steps == 17                      # outstanding drained, on budget
    assert ev.outstanding() == 0


# =============================================================================
# Out-of-order completion accounting
# =============================================================================
def test_account_records_completion_order():
    acct = EvalAccount()
    acct.record_completion(5, 3.0, cost=3.0, finished_at=3.0)
    acct.record_completion(4, 1.0, cost=9.0, finished_at=4.0)
    assert acct.steps == 2
    assert acct.elapsed == 4.0                 # completion frontier, not sum
    assert acct.busy == 12.0                   # worker-seconds ARE the sum
    assert acct.trace == [(1, 3.0, 3.0), (2, 4.0, 1.0)]
    assert acct.best_index == 4


def test_virtual_async_out_of_order(gemm_recorded):
    """A cheap config submitted after an expensive one finishes first."""
    ev = VirtualAsyncEvaluator(ReplayEvaluator(gemm_recorded), workers=2)
    rts = gemm_recorded.runtimes
    slow, fast = int(np.argmax(rts)), int(np.argmin(rts))
    ev.submit([Candidate(slow), Candidate(fast)])
    first = ev.collect()[0]
    second = ev.collect()[0]
    assert first.index == fast and second.index == slow
    times = [t for _, t, _ in ev.trace]
    assert times == sorted(times)              # trace in completion order
    assert ev.elapsed < ev.busy                # 2 lanes compressed the clock


def test_virtual_async_single_worker_matches_sequential(gemm_recorded):
    """workers=1 degrades to the sequential cost model exactly."""
    ev_async = VirtualAsyncEvaluator(ReplayEvaluator(gemm_recorded),
                                     workers=1)
    ev_seq = ReplayEvaluator(gemm_recorded)
    for idx in (3, 11, 7):
        ev_async.submit([Candidate(idx)])
        ev_async.collect()
        ev_seq.measure(idx)
    assert ev_async.trace == ev_seq.trace
    assert ev_async.elapsed == ev_seq.elapsed


def test_default_shim_submit_collect_matches_measure_many(gemm_recorded):
    ev_a, ev_b = ReplayEvaluator(gemm_recorded), ReplayEvaluator(gemm_recorded)
    cands = [Candidate(2), Candidate(9), Candidate(4)]
    ev_a.submit(cands)
    obs_a = ev_a.collect()
    obs_b = ev_b.measure_many(cands)
    assert obs_a == obs_b
    assert ev_a.trace == ev_b.trace
    assert ev_a.outstanding() == 0


# =============================================================================
# Fleet orchestration
# =============================================================================
def _serve_jobs(hw: str, budget: int = 25, seed: int = 7):
    jobs = []
    for bucket, (plen, new) in BUCKET_SHAPES.items():
        jobs.append(TuningJob(
            name=f"serve/{bucket}@{hw}", space=serve_space(),
            workload_fn=serve_workload_fn(16, plen, new, STATS),
            hardware=hw, bucket=bucket, budget=budget, seed=seed))
    return jobs


def _well_threshold(bucket: str, hw: str) -> float:
    plen, new = BUCKET_SHAPES[bucket]
    rec = record_space(serve_space(),
                       serve_workload_fn(16, plen, new, STATS), SPECS[hw])
    return rec.best_runtime * 1.1


def test_fleet_shares_store_and_warm_starts(tmp_path):
    """3 jobs × 2 hardware targets, one store: wave 2 warm-starts from the
    wave-1 artifacts and converges in ≤ half the cold trials."""
    store = ConfigStore(str(tmp_path / "fleet.json"))
    pool = VirtualWorkerPool(workers=4)
    rep1 = FleetTuner(_serve_jobs("tpu_v4"), pool, store=store,
                      in_flight=4).run()
    rep2 = FleetTuner(_serve_jobs("tpu_v5e"), pool, store=store,
                      in_flight=4).run()
    assert all(not r.warm_started for r in rep1.results)
    assert all(r.warm_started for r in rep2.results)
    assert len(store) == 6                        # one entry per job
    cold = warm = 0
    for hw, rep in (("tpu_v4", rep1), ("tpu_v5e", rep2)):
        for r in rep.results:
            t = r.trials_to_threshold(_well_threshold(r.bucket, hw))
            assert t is not None
            if r.warm_started:
                warm += t
            else:
                cold += t
    assert warm <= cold / 2                       # the amortization claim
    # the store survives a restart with both hardware keys populated
    again = ConfigStore(str(tmp_path / "fleet.json"))
    assert again.get("serve_online", "p1n1", "tpu_v4") is not None
    assert again.get("serve_online", "p1n1", "tpu_v5e") is not None


def test_fleet_wall_clock_beats_sequential():
    """Same jobs, same budgets: 4 workers compress the virtual wall-clock."""
    def run(workers):
        jobs = _serve_jobs("tpu_v4", budget=20)
        for j in jobs:
            j.searcher = "random"                 # identical work both ways
        pool = VirtualWorkerPool(workers=workers)
        return FleetTuner(jobs, pool, store=None, in_flight=workers,
                          publish_models=False).run()
    seq, fleet = run(1), run(4)
    assert abs(seq.busy - fleet.busy) < 1e-9      # identical measurements
    assert fleet.elapsed < seq.elapsed / 2        # ≥2x compressed (conserv.)


def test_fleet_thread_pool_runs():
    """ThreadWorkerPool end-to-end with a blocking eval_fn."""
    import time as _time

    def eval_fn(index, profile):
        _time.sleep(0.002)
        return 0.001 * (index + 1), None, 0.002
    jobs = [TuningJob(name=f"j{i}", space=serve_space(),
                      workload_fn=None, hardware="tpu_v4", budget=6,
                      seed=i, searcher="random", eval_fn=eval_fn)
            for i in range(3)]
    pool = ThreadWorkerPool(workers=4)
    try:
        rep = FleetTuner(jobs, pool, store=None,
                         publish_models=False).run()
    finally:
        pool.close()
    assert sorted(r.trials for r in rep.results) == [6, 6, 6]
    for r in rep.results:
        assert r.best_runtime == min(rt for _, rt in r.history)


def test_fleet_rejects_duplicate_job_names():
    jobs = _serve_jobs("tpu_v4")[:1] * 2
    with pytest.raises(ValueError):
        FleetTuner(jobs, VirtualWorkerPool(1))


def test_fleet_schedules_jobs_round_robin():
    """The first fill wave spreads lanes across jobs, not 2 lanes to one
    job and 0 to another (regression: cursor skew in the fill loop)."""
    submitted = []

    class RecordingPool(VirtualWorkerPool):
        def submit(self, item):
            submitted.append(item.job)
            super().submit(item)

    jobs = _serve_jobs("tpu_v4", budget=8)
    for j in jobs:
        j.searcher = "random"
    FleetTuner(jobs, RecordingPool(workers=4), store=None,
               in_flight=4, publish_models=False).run()
    names = [j.name for j in jobs]
    assert submitted[:4] == [names[0], names[1], names[2], names[0]]


def test_fleet_job_results_use_run_relative_clock():
    """A pool reused across runs must not leak its clock into per-job
    accounts: every job's elapsed stays within the run's own makespan."""
    pool = VirtualWorkerPool(workers=4)
    FleetTuner(_serve_jobs("tpu_v4", budget=10), pool, store=None,
               publish_models=False).run()
    rep2 = FleetTuner(_serve_jobs("tpu_v5e", budget=10), pool, store=None,
                      publish_models=False).run()
    for r in rep2.results:
        assert 0.0 < r.elapsed <= rep2.elapsed + 1e-12
        assert all(0.0 <= t <= rep2.elapsed + 1e-12
                   for _, t, _ in r.trace)


# =============================================================================
# Incremental fleet API (begin/step/finish, add/cancel/stop) — ISSUE 6
# =============================================================================
def test_fleet_incremental_loop_matches_run():
    """``run()`` is exactly begin + step-until-idle + finish; a manual
    incremental drive must produce bit-identical per-job results."""
    rep_run = FleetTuner(_serve_jobs("tpu_v4", budget=12),
                         VirtualWorkerPool(workers=4), store=None,
                         publish_models=False, in_flight=4).run()
    tuner = FleetTuner(_serve_jobs("tpu_v4", budget=12),
                       VirtualWorkerPool(workers=4), store=None,
                       publish_models=False, in_flight=4)
    tuner.begin()
    while tuner.step():
        pass
    rep_inc = tuner.finish()
    by_job = {r.job: r for r in rep_run.results}
    assert len(rep_inc.results) == len(rep_run.results)
    for r in rep_inc.results:
        ref = by_job[r.job]
        assert r.trace == ref.trace
        assert r.best_index == ref.best_index
        assert r.best_runtime == ref.best_runtime
    assert rep_inc.elapsed == rep_run.elapsed


def test_fleet_add_job_while_running():
    """A service fleet starts empty and takes jobs mid-flight."""
    done = []
    tuner = FleetTuner([], VirtualWorkerPool(workers=2), store=None,
                       publish_models=False, allow_empty=True,
                       on_job_done=lambda r: done.append(r.job))
    tuner.begin()
    jobs = _serve_jobs("tpu_v4", budget=6)
    tuner.add_job(jobs[0])
    for _ in range(4):
        tuner.step(max_wait=0.01)
    tuner.add_job(jobs[1])               # injected while job 0 is in flight
    while tuner.step(max_wait=0.01):
        pass
    rep = tuner.finish()
    assert sorted(done) == sorted(j.name for j in jobs[:2])
    assert all(not r.cancelled and r.trials == 6 for r in rep.results)
    with pytest.raises(ValueError):      # duplicate names still rejected
        tuner.add_job(jobs[0])


def test_fleet_cancel_job_mid_run(tmp_path):
    """Cancelling abandons in-flight tests, bills their cost, resolves a
    partial ``cancelled`` result, and publishes nothing for that job."""
    store = ConfigStore(str(tmp_path / "s.json"))
    jobs = _serve_jobs("tpu_v4", budget=20)[:2]    # < space size (25)
    tuner = FleetTuner(jobs, VirtualWorkerPool(workers=2), store=store,
                       in_flight=2)
    tuner.begin()
    for _ in range(3):
        tuner.step(max_wait=0.01)
    assert tuner.cancel_job(jobs[0].name)
    assert not tuner.cancel_job(jobs[0].name)     # already resolved
    assert not tuner.cancel_job("no_such_job")
    while tuner.step(max_wait=0.01):
        pass
    rep = tuner.finish()
    by_job = {r.job: r for r in rep.results}
    cancelled = by_job[jobs[0].name]
    survivor = by_job[jobs[1].name]
    assert cancelled.cancelled and cancelled.trials < 20
    assert not survivor.cancelled and survivor.trials == 20
    # only the surviving job published to the store
    assert store.get("serve_online", survivor.bucket, "tpu_v5e") is None
    assert store.get("serve_online", survivor.bucket, "tpu_v4") is not None
    assert store.get("serve_online", cancelled.bucket, "tpu_v4") is None


def test_fleet_stop_drains_in_flight():
    """``stop()`` collects what is already on the pool (billed to busy)
    but submits nothing new; unfinished jobs resolve as cancelled."""
    tuner = FleetTuner(_serve_jobs("tpu_v4", budget=40),
                       VirtualWorkerPool(workers=4), store=None,
                       publish_models=False, in_flight=4)
    tuner.begin()
    tuner.step(max_wait=0.01)
    assert not tuner.stopping
    tuner.stop()
    assert tuner.stopping
    while tuner.step(max_wait=0.01):
        pass
    rep = tuner.finish()
    assert all(r.cancelled for r in rep.results)
    assert all(r.trials < 40 for r in rep.results)
    total_trials = sum(r.trials for r in rep.results)
    assert 0 < total_trials <= 8         # first fill wave only (4 + refills)
    assert rep.busy > 0.0


def test_fleet_progress_snapshot():
    tuner = FleetTuner(_serve_jobs("tpu_v4", budget=6),
                       VirtualWorkerPool(workers=2), store=None,
                       publish_models=False)
    tuner.begin()
    p0 = tuner.progress()
    assert p0["jobs"] == 3 and p0["jobs_done"] == 0
    while tuner.step(max_wait=0.01):
        pass
    tuner.finish()
    p1 = tuner.progress()
    assert p1["jobs_done"] == 3
    assert p1["busy_s"] > 0.0 and 0.0 < p1["utilization"] <= 1.0


def test_unregistered_hardware_ships_spec_payload():
    """Fingerprint store keys can't be resolved by name in a worker
    subprocess, so payloads carry the spec's numbers instead."""
    import dataclasses as dc

    from repro.fleet.tuner import _JobState

    custom = dc.replace(SPECS["tpu_v4"], name="lab_chip")
    job = job_from_registry("matmul", "128", "tpu_v4", budget=4)
    job.hardware = custom
    js = _JobState(job)
    payload = js.payload_for(0, False)
    assert "hw" not in payload
    assert hwspec_roundtrip(payload["hw_spec"]) == custom
    # registered hardware still travels by (normalized) name
    js2 = _JobState(job_from_registry("matmul", "128", "TPUv4", budget=4))
    assert js2.payload_for(0, False)["hw"] == "tpu_v4"


def hwspec_roundtrip(d):
    from repro.core.hwspec import HardwareSpec
    return HardwareSpec(**d)


# =============================================================================
# Hardware naming drift / fingerprint keys
# =============================================================================
def test_hwspec_get_tolerates_naming_drift():
    assert get("TPUv4") is SPECS["tpu_v4"]
    assert get("tpu-v4") is SPECS["tpu_v4"]
    assert get("TPU_V5E") is SPECS["tpu_v5e"]
    with pytest.raises(KeyError):
        get("gtx_9000")


def test_hardware_key_normalizes():
    assert hardware_key("TPUv4") == "tpu_v4"
    assert hardware_key(SPECS["tpu_v4"]) == "tpu_v4"
    assert hardware_key("tpu_v4") == hardware_key("TPU-v4")
    assert normalize_name("My GPU (rev B)") == "my_gpu_rev_b"


def test_hardware_key_fingerprints_unregistered_spec():
    import dataclasses
    custom = dataclasses.replace(SPECS["tpu_v4"], name="lab_chip")
    key = hardware_key(custom)
    assert key == fingerprint(custom)
    assert "lab_chip" in key and key == hardware_key(custom)  # stable


def test_store_hits_survive_naming_drift(tmp_path):
    """The satellite's end-to-end claim: drifted names share entries."""
    store = ConfigStore(str(tmp_path / "s.json"))
    store.put("sp", "b", hardware_key("TPUv4"), config={"X": 1},
              runtime=1.0, trials=3)
    assert store.get("sp", "b", hardware_key("tpu_v4")) is not None
    assert store.get("sp", "b", hardware_key(SPECS["tpu_v4"])) is not None


# =============================================================================
# Nearest-model lookup
# =============================================================================
def test_nearest_model_preference_order(gemm_recorded):
    model = train_model(gemm_recorded, kind="tree")
    space = gemm_recorded.space
    store = ConfigStore()
    store.save_model(space.name, "bucketA", "hw1", model, space)
    store.save_model(space.name, "bucketB", "hw2", model, space)
    # exact
    assert store.nearest_model_key(space.name, "bucketA", "hw1") \
        == f"kernel|{space.name}|bucketA|hw1"
    # same bucket, other hardware beats same hardware, other bucket
    assert store.nearest_model_key(space.name, "bucketA", "hw2") \
        == f"kernel|{space.name}|bucketA|hw1"
    # same hardware, other bucket
    assert store.nearest_model_key(space.name, "bucketC", "hw2") \
        == f"kernel|{space.name}|bucketB|hw2"
    # any model of the space
    assert store.nearest_model_key(space.name, "bucketC", "hw9") \
        == f"kernel|{space.name}|bucketA|hw1"
    # unknown space: nothing
    assert store.nearest_model_key("other_space", "b", "h") is None
    m, key = store.load_nearest_model(space.name, "bucketA", "hw2",
                                      bind_space=space)
    assert m is not None and key.endswith("bucketA|hw1")


# =============================================================================
# Serving tuner through the async driver
# =============================================================================
def test_online_autotuner_in_flight_matches_sequential(tmp_path):
    """With the synchronous backend shim, in_flight>1 tunes identically."""
    from repro.serve.autotune import OnlineAutotuner, SyntheticServeBackend
    from repro.serve.engine import Request

    def run(in_flight, path):
        backend = SyntheticServeBackend(SPECS["tpu_v4"], STATS, seed=0)
        tuner = OnlineAutotuner(backend, store=ConfigStore(path),
                                hw=SPECS["tpu_v4"], stats=STATS,
                                in_flight=in_flight, seed=0)
        reqs = [Request(uid=i, prompt=np.ones(12, np.int32),
                        max_new_tokens=6) for i in range(8)]
        _, rep = tuner.serve(reqs)
        return rep
    r1 = run(1, str(tmp_path / "a.json"))
    r4 = run(4, str(tmp_path / "b.json"))
    assert r1.config == r4.config
    assert r1.history == r4.history


# =============================================================================
# Subprocess worker backend (slow: spawns interpreters)
# =============================================================================
@pytest.mark.slow
def test_subprocess_pool_matches_virtual():
    """2 worker processes agree with the in-process virtual backend on
    what they measured."""
    from repro.fleet import SubprocessWorkerPool

    def jobs():
        return [job_from_registry("matmul", "128", hw, budget=8, seed=3,
                                  searcher="random")
                for hw in ("tpu_v4", "tpu_v5e")]

    pool = SubprocessWorkerPool(workers=2)
    try:
        rep_sub = FleetTuner(jobs(), pool, store=None,
                             publish_models=False).run()
    finally:
        pool.close()
    rep_virt = FleetTuner(jobs(), VirtualWorkerPool(workers=2), store=None,
                          publish_models=False).run()
    sub = {r.job: r for r in rep_sub.results}
    virt = {r.job: r for r in rep_virt.results}
    for name in sub:
        assert sub[name].trials == virt[name].trials
        # same configs measured to the same runtimes (cost model is pure)
        assert sorted(sub[name].history) == sorted(virt[name].history)
        assert sub[name].best_runtime == pytest.approx(
            virt[name].best_runtime)
