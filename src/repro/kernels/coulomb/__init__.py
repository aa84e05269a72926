import jax.numpy as jnp
import numpy as np

from repro.kernels.coulomb.kernel import coulomb
from repro.kernels.coulomb.ref import coulomb_ref
from repro.kernels.coulomb.space import make_space, workload_fn, DEFAULT_INPUT
from repro.kernels.registry import KernelBenchmark, register_benchmark


def _make_args(inp, rng):
    atoms = rng.uniform(0.0, inp.grid_size * 0.5,
                        (inp.n_atoms, 4)).astype(np.float32)
    atoms[:, 3] = rng.uniform(0.1, 1.0, inp.n_atoms)
    return (jnp.asarray(atoms),)


@register_benchmark("coulomb")
def _benchmark() -> KernelBenchmark:
    from repro.kernels.coulomb import ops, space

    return KernelBenchmark(
        name="coulomb",
        make_space=space.make_space,
        workload_fn=space.workload_fn,
        default_input=space.DEFAULT_INPUT,
        inputs={
            "default": space.DEFAULT_INPUT,
            "large_grid": space.LARGE_GRID,
            "small_grid": space.SMALL_GRID,
        },
        make_args=_make_args, run=ops.run, ref=coulomb_ref,
        default_config={"Z_IT": 4, "BY": 8, "BX": 128, "ATOM_CHUNK": 16,
                        "ATOMS_IN_SMEM": 1},
    )
