"""Sharding rules, HLO cost parser, and multi-device integration
(the 512-device dry-run path is covered by launch/dryrun.py; here we check
the machinery on small in-process examples + an 8-device subprocess)."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro.distributed.sharding import ShardingRules, default_rules, spec_for
from repro.roofline.analysis import parse_collectives
from repro.roofline.hlo_parse import analyze


class _FakeMesh:
    shape = {"data": 16, "model": 16}


def test_spec_for_divisibility():
    rules = default_rules(multi_pod=False)
    mesh = _FakeMesh()
    # divisible dims shard; non-divisible are dropped (replicated)
    s = spec_for(mesh, rules, ("vocab", "embed"), (256000, 4096))
    assert s == jax.sharding.PartitionSpec("model", "data")
    s = spec_for(mesh, rules, ("kv", None), (8, 64))   # 8 kv heads vs 16-way
    assert s == jax.sharding.PartitionSpec()


def test_spec_for_no_double_axis_use():
    rules = default_rules(multi_pod=False)
    s = spec_for(_FakeMesh(), rules, ("mlp", "heads"), (1024, 1024))
    # both map to "model": the second must be dropped
    assert s == jax.sharding.PartitionSpec("model")


def test_hlo_parser_scales_scan_bodies():
    def f(x, ws):
        def body(h, w):
            return jnp.tanh(h @ w), None
        h, _ = jax.lax.scan(body, x, ws)
        return h

    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    ws = jax.ShapeDtypeStruct((7, 128, 128), jnp.float32)
    compiled = jax.jit(f).lower(x, ws).compile()
    cost = analyze(compiled.as_text())
    expect = 7 * 2 * 128 ** 3
    assert abs(cost.flops - expect) / expect < 0.05


def test_hlo_parser_transcendentals():
    def f(x):
        return jnp.exp(x).sum()
    compiled = jax.jit(f).lower(
        jax.ShapeDtypeStruct((1024,), jnp.float32)).compile()
    cost = analyze(compiled.as_text())
    assert cost.transcendentals >= 1024


def test_parse_collectives_text():
    hlo = """
  %ar = f32[1024]{0} all-reduce(%x), replica_groups={}, to_apply=%add
  %ag.1 = bf16[64,128]{1,0} all-gather(%y), dimensions={0}
"""
    st = parse_collectives(hlo)
    assert st.count_by_op == {"all-reduce": 1, "all-gather": 1}
    assert st.bytes_by_op["all-reduce"] == 4096.0
    assert st.bytes_by_op["all-gather"] == 64 * 128 * 2


_SUBPROCESS_PROG = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import SMOKES
    from repro.models.registry import build_model
    from repro.optim.adamw import AdamW, constant_lr
    from repro.train.train_step import StepConfig, init_train_state, make_train_step
    from repro.distributed.sharding import default_rules, param_shardings
    from repro.distributed.api import activation_sharding
    from repro.distributed.sharding import make_act_resolver
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(4, 2)
    rules = default_rules(multi_pod=False)
    model = build_model(SMOKES["qwen2.5-3b"])
    opt = AdamW(lr=constant_lr(1e-3))
    step = make_train_step(model, opt, StepConfig(remat="none"))
    with mesh:
        with activation_sharding(make_act_resolver(mesh, rules)):
            state = init_train_state(model, opt, jax.random.PRNGKey(0))
            p_sh = param_shardings(mesh, rules, model.specs(), state.params)
            state = state._replace(params=jax.tree.map(jax.device_put, state.params, p_sh))
            npr = np.random.default_rng(0)
            batch = {
                "tokens": jnp.asarray(npr.integers(0, 512, (8, 32)), jnp.int32),
                "labels": jnp.asarray(npr.integers(0, 512, (8, 32)), jnp.int32),
            }
            state, metrics = jax.jit(step)(state, batch)
            loss = float(metrics["loss"])
            assert np.isfinite(loss), loss
            print("MULTIDEV_OK", loss)
""")


@pytest.mark.slow
def test_multidevice_train_step_subprocess():
    """Real 8-device SPMD execution (numerics, not just compile) — by far
    the suite's single slowest test (minutes of subprocess XLA compiles)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, "-c", _SUBPROCESS_PROG],
        capture_output=True, text=True, timeout=600,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": os.environ.get("HOME", repo), "JAX_PLATFORMS": "cpu"},
        cwd=repo,
    )
    assert "MULTIDEV_OK" in r.stdout, r.stdout + r.stderr
