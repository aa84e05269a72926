"""The main-path Pallas kernels, and the serving decode's cache layout, compile
for a described TPU v5e.

No chip is needed: the TPU compiler is installed and compiles for a
topology that is described, not attached.  This catches what interpret
mode cannot (block shapes off the (8, 128) tiling, unaligned DMAs, VMEM
overflow, ops Mosaic does not lower).  The topology is described inside a
module fixture, never at import: only one process may load the TPU
library, and every test worker imports this file.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.registry import BENCHMARKS

KERNELS = ("attention", "conv2d", "coulomb", "matmul", "nbody", "transpose")


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip cannot be read back from the
    # persistent cache here, so keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # no compiler logs in /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _arg_structs(name, inp, sharding):
    def f32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)

    if name == "matmul":
        return (f32(inp.m, inp.k), f32(inp.k, inp.n)), {}
    if name == "transpose":
        return (f32(inp.m, inp.n),), {}
    if name == "coulomb":
        return (f32(inp.n_atoms, 4),), {"grid_size": inp.grid_size}
    if name == "nbody":
        return (f32(inp.n, 4),), {}
    if name == "conv2d":
        return (f32(inp.h, inp.w), f32(inp.f, inp.f)), {}
    shape = (inp.batch, inp.heads, inp.seq, inp.head_dim)
    return (f32(*shape), f32(*shape), f32(*shape)), {}


def _compile(name, cfg, sharding):
    bm = BENCHMARKS[name]
    args, kw = _arg_structs(name, bm.default_input, sharding)
    return jax.jit(lambda *a: bm.run(cfg, *a, **kw)).lower(*args).compile()


@pytest.mark.parametrize("name", KERNELS)
def test_default_config_compiles_for_v5e(name, one_chip):
    compiled = _compile(name, BENCHMARKS[name].default_config, one_chip)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("unroll", [0, 1])
def test_conv2d_tap_modes_compile_for_v5e(unroll, one_chip):
    cfg = dict(BENCHMARKS["conv2d"].default_config, UNROLL_TAPS=unroll)
    _compile("conv2d", cfg, one_chip)


def test_matmul_block_n_64_refused_for_v5e(one_chip):
    cfg = dict(BENCHMARKS["matmul"].default_config, BLOCK_N=64)
    with pytest.raises(Exception, match="divisible by 8 and 128"):
        _compile("matmul", cfg, one_chip)


def test_qwen_decode_writes_its_token_in_one_cache_row_for_v5e(one_chip):
    """qwen1.5-0.5b's decode (16 x 1024, the cache donated, as the engine
    compiles it): no write into the K/V cache has the sequence axis
    minor-most in its layout, where a one-token write would touch every
    tile, and no temp buffer could hold a copy of a cache."""
    from repro.configs import ARCHS
    from repro.models.registry import build_model

    batch, max_seq = 16, 1024
    m = build_model(ARCHS["qwen1.5-0.5b"])

    def struct(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    params = jax.tree.map(struct, jax.eval_shape(m.init,
                                                 jax.random.PRNGKey(0)))
    cache = jax.tree.map(struct, m.cache_specs(batch, max_seq))
    tokens = jax.ShapeDtypeStruct((batch, 1), jnp.int32, sharding=one_chip)

    def decode(params, cache, batch):
        logits, cache = m.decode(params, cache, batch)
        return jnp.argmax(logits[:, -1:], axis=-1), cache

    compiled = jax.jit(decode, donate_argnums=(1,)).lower(
        params, cache, {"tokens": tokens}).compile()
    shape = cache["k"].shape
    seq_axis = shape.index(max_seq)
    dims = ",".join(map(str, shape))
    layouts = re.findall(
        rf"= bf16\[{dims}\]\{{([\d,]+):[^}}]*\}} dynamic-update-slice\(",
        compiled.as_text())
    assert len(layouts) == 2, layouts              # the k and the v write
    for layout in layouts:
        assert int(layout.split(",")[0]) != seq_axis, layout
    assert compiled.memory_analysis().temp_size_in_bytes < 8 * 2 ** 20
