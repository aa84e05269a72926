"""Jit'd wrapper: tuning-config dict -> transpose kernel invocation."""
from repro.kernels.transpose.kernel import transpose


def run(cfg, x, interpret: bool = False):
    return transpose(x, block_m=cfg["BLOCK_M"], block_n=cfg["BLOCK_N"],
                     interpret=interpret)
