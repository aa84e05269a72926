import jax.numpy as jnp
import numpy as np

from repro.kernels.matmul.kernel import matmul
from repro.kernels.matmul.ref import matmul_ref
from repro.kernels.matmul.space import make_space, workload_fn, DEFAULT_INPUT
from repro.kernels.registry import KernelBenchmark, register_benchmark


def _make_args(inp, rng):
    a = jnp.asarray(rng.standard_normal((inp.m, inp.k), dtype=np.float32))
    b = jnp.asarray(rng.standard_normal((inp.k, inp.n), dtype=np.float32))
    return (a, b)


@register_benchmark("matmul")
def _benchmark() -> KernelBenchmark:
    from repro.kernels.matmul import ops, space

    return KernelBenchmark(
        name="matmul",
        make_space=space.make_space,
        workload_fn=space.workload_fn,
        default_input=space.DEFAULT_INPUT,
        inputs={
            "2048": space.DEFAULT_INPUT,
            "128": space.SQUARE_SMALL,
            "16x4096": space.RECT_TALL,
            "4096x16": space.RECT_WIDE,
        },
        make_args=_make_args, run=ops.run, ref=matmul_ref,
        default_config={"BLOCK_M": 256, "BLOCK_N": 256, "BLOCK_K": 512,
                        "LOOP_ORDER": "mnk", "ACC_F32": 1},
    )
