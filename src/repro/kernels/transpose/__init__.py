import jax.numpy as jnp
import numpy as np

from repro.kernels.transpose.kernel import transpose
from repro.kernels.transpose.ref import transpose_ref
from repro.kernels.transpose.space import make_space, workload_fn, DEFAULT_INPUT
from repro.kernels.registry import KernelBenchmark, register_benchmark


def _make_args(inp, rng):
    return (jnp.asarray(rng.standard_normal((inp.m, inp.n), dtype=np.float32)),)


@register_benchmark("transpose")
def _benchmark() -> KernelBenchmark:
    from repro.kernels.transpose import ops, space

    return KernelBenchmark(
        name="transpose",
        make_space=space.make_space,
        workload_fn=space.workload_fn,
        default_input=space.DEFAULT_INPUT,
        inputs={"8192": space.DEFAULT_INPUT},
        make_args=_make_args, run=ops.run, ref=transpose_ref,
        default_config={"BLOCK_M": 256, "BLOCK_N": 256, "STAGE_OUT": 0},
    )
