import jax.numpy as jnp
import numpy as np

from repro.kernels.nbody.kernel import nbody
from repro.kernels.nbody.ref import nbody_ref
from repro.kernels.nbody.space import make_space, workload_fn, DEFAULT_INPUT
from repro.kernels.registry import KernelBenchmark, register_benchmark


def _make_args(inp, rng):
    b = rng.standard_normal((inp.n, 4)).astype(np.float32)
    b[:, 3] = np.abs(b[:, 3]) + 0.1
    return (jnp.asarray(b),)


@register_benchmark("nbody")
def _benchmark() -> KernelBenchmark:
    from repro.kernels.nbody import ops, space

    return KernelBenchmark(
        name="nbody",
        make_space=space.make_space,
        workload_fn=space.workload_fn,
        default_input=space.DEFAULT_INPUT,
        inputs={
            "16k": space.DEFAULT_INPUT,
            "131k": space.LARGE_INPUT,
        },
        make_args=_make_args, run=ops.run, ref=nbody_ref,
        default_config={"BLOCK_I": 256, "BLOCK_J": 256, "J_UNROLL": 1,
                        "KEEP_PAIRWISE": 0},
    )
