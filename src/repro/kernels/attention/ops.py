"""Jit'd wrapper: tuning-config dict -> flash attention invocation."""
from repro.kernels.attention.kernel import flash_attention


def run(cfg, q, k, v, interpret: bool = False):
    return flash_attention(q, k, v, block_q=cfg["BLOCK_Q"],
                           block_k=cfg["BLOCK_K"], interpret=interpret)
