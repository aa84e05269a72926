import jax.numpy as jnp
import numpy as np

from repro.kernels.conv2d.kernel import conv2d
from repro.kernels.conv2d.ref import conv2d_ref
from repro.kernels.conv2d.space import make_space, workload_fn, DEFAULT_INPUT
from repro.kernels.registry import KernelBenchmark, register_benchmark


def _make_args(inp, rng):
    img = jnp.asarray(rng.standard_normal((inp.h, inp.w), dtype=np.float32))
    flt = jnp.asarray(rng.standard_normal((inp.f, inp.f), dtype=np.float32))
    return (img, flt)


@register_benchmark("conv2d")
def _benchmark() -> KernelBenchmark:
    from repro.kernels.conv2d import ops, space

    return KernelBenchmark(
        name="conv2d",
        make_space=space.make_space,
        workload_fn=space.workload_fn,
        default_input=space.DEFAULT_INPUT,
        inputs={"4096": space.DEFAULT_INPUT},
        make_args=_make_args, run=ops.run, ref=conv2d_ref,
        default_config={"BY": 128, "BX": 256, "UNROLL_TAPS": 1,
                        "FILTER_SMEM": 1, "DMA_DEPTH": 1},
    )
