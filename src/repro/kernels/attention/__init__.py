import jax.numpy as jnp
import numpy as np

from repro.kernels.attention.kernel import flash_attention, flash_attention_single_head
from repro.kernels.attention.ref import attention_ref
from repro.kernels.attention.space import make_space, workload_fn, DEFAULT_INPUT
from repro.kernels.registry import KernelBenchmark, register_benchmark


def _make_args(inp, rng):
    shape = (inp.batch, inp.heads, inp.seq, inp.head_dim)
    mk = lambda: jnp.asarray(
        rng.standard_normal(shape, dtype=np.float32) * 0.3)
    return (mk(), mk(), mk())


@register_benchmark("attention")
def _benchmark() -> KernelBenchmark:
    from repro.kernels.attention import ops, space

    return KernelBenchmark(
        name="attention",
        make_space=space.make_space,
        workload_fn=space.workload_fn,
        default_input=space.DEFAULT_INPUT,
        inputs={"default": space.DEFAULT_INPUT},
        make_args=_make_args, run=ops.run, ref=attention_ref,
        default_config={"BLOCK_Q": 256, "BLOCK_K": 256, "KEEP_P": 0, "Q_PREFETCH": 1},
    )
