"""Jit'd wrapper: tuning-config dict -> conv2d kernel invocation."""
from repro.kernels.conv2d.kernel import conv2d


def run(cfg, img, flt, interpret: bool = False):
    return conv2d(img, flt, by=cfg["BY"], bx=cfg["BX"],
                  unroll_taps=bool(cfg["UNROLL_TAPS"]), interpret=interpret)
