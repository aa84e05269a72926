"""Jit'd wrapper: tuning-config dict -> N-body kernel invocation."""
from repro.kernels.nbody.kernel import nbody


def run(cfg, bodies, interpret: bool = False):
    return nbody(bodies, block_i=cfg["BLOCK_I"], block_j=cfg["BLOCK_J"],
                 interpret=interpret)
