"""Jit'd wrapper: tuning-config dict -> Coulomb kernel invocation."""
from repro.kernels.coulomb.kernel import coulomb


def run(cfg, atoms, *, grid_size: int, interpret: bool = False):
    return coulomb(atoms, grid_size=grid_size, z_it=cfg["Z_IT"],
                   by=cfg["BY"], bx=cfg["BX"], atom_chunk=cfg["ATOM_CHUNK"],
                   interpret=interpret)
