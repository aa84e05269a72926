"""The trace reduction, on hand-made intervals, on a trace recorded here on
the CPU, and on a trimmed trace of a serving window recorded on a TPU v5e
(``data/serve_trace.json.gz``, the ``extract`` form of the file)."""
import os

import numpy as np
import pytest

from bench import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "serve_trace.json.gz")


def test_interval_arithmetic():
    u = trace.union([(5, 7), (0, 2), (1, 3), (6, 9)])
    assert u == [(0, 3), (5, 9)]
    assert trace.measure(u, 2, 6) == 2
    assert trace.subtract(u, [(1, 6)]) == [(0, 1), (6, 9)]
    assert trace.subtract([(0, 10)], [(2, 3), (5, 7)]) == [
        (0, 2), (3, 5), (7, 10)]


def test_collective_exposure_and_gaps_on_hand_made_events():
    ex = {"ops": {"/device:TPU:0": [
              ["fusion.1", 0, 10], ["all-reduce.2", 5, 10],
              ["fusion.3", 30, 10]]},
          "programs": {"/device:TPU:0": [["jit_step(123)", 0, 30]]},
          "host": [["tick:a", 0, 100], ["make_requests", 14, 20]]}
    tr = trace.Trace(ex, 0, 50)
    assert tr.busy_ns() == 25                      # 0..15 and 30..40
    assert tr.collective_exposed_ns() == 5          # 10..15
    assert tr.op_time() == {"jit_step/fusion.1": 10,
                            "jit_step/all-reduce.2": 10, "?/fusion.3": 10}
    loop = trace.Trace({"ops": {"d": [["while.1", 0, 20], ["fusion.2", 0, 5],
                                      ["fusion.3", 6, 9]]},
                        "programs": {"d": [["jit_f(9)", 0, 20]]},
                        "host": []}, 0, 20)
    assert loop.op_time() == {"jit_f/fusion.2": 5, "jit_f/fusion.3": 9}
    assert loop.busy_ns() == 20
    assert tr.idle_gaps() == [("make_requests", 15), ("tick:a", 10)]
    assert tr.program_calls("step") == [(0, 30)]


def test_extract_reads_host_spans_of_a_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path), profiler_options=trace.options())
    with jax.profiler.TraceAnnotation("tick:chat"):
        jnp.ones(8).block_until_ready()
    jax.profiler.stop_trace()
    ex = trace.extract(trace.find(str(tmp_path)), ("tick:",))
    assert [e[0] for e in ex["host"]] == ["tick:chat"]
    assert ex["host"][0][2] > 0


def brute_busy(ex, lo, hi, step=1000.0):
    """Busy ns of each device by sampling time on a grid."""
    grid = np.arange(lo, hi, step) + step / 2
    out = []
    for evs in ex["ops"].values():
        hit = np.zeros(len(grid), bool)
        for _, s, d in evs:
            hit |= (grid >= s) & (grid < s + d)
        out.append(hit.sum() * step)
    return np.mean(out)


@pytest.mark.skipif(not os.path.exists(DATA), reason="no recorded trace")
def test_reduction_of_a_recorded_chip_trace():
    ex = trace.load(DATA)
    lo, hi = ex["window"]
    tr = trace.Trace(ex, lo, hi)
    assert tr.devices == ["/device:TPU:0"]
    busy = tr.busy_ns()
    assert 0 < busy < tr.window_ns
    assert busy == pytest.approx(brute_busy(ex, lo, hi), rel=0.01)
    gaps = tr.idle_gaps()
    assert sum(g for _, g in gaps) == pytest.approx(tr.window_ns - busy)
    assert {label for label, _ in gaps} <= {"none"} | {
        e[0] for e in ex["host"]}
    assert tr.collective_exposed_ns() == 0
    decode = tr.program_calls("jit_decode")
    assert decode and all(d > 0 for _, d in decode)
