"""Virtual TPU hardware specifications (paper §4.2, Table 3 — adapted).

The paper evaluates portability across four NVIDIA generations (Kepler,
Maxwell, Pascal, Turing).  We use four TPU generations with distinct
flop-to-byte ratios and VMEM capacities, so a kernel that is compute-bound on
one is memory-bound on another — exactly the property the paper exploits
(PC_stress varies across hardware; PC_ops does not).

Numbers are public per-chip peaks.  ``v5e`` is the production dry-run target
(197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link ICI per the assignment).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Tuple, Union


@dataclasses.dataclass(frozen=True)
class HardwareSpec:
    name: str
    generation: str
    # peak dense matmul throughput, FLOP/s (bf16)
    mxu_flops: float
    # peak vector unit throughput, op/s
    vpu_flops: float
    # transcendental throughput, op/s (slow VPU path)
    trans_flops: float
    hbm_bw: float          # bytes/s
    vmem_bw: float         # bytes/s (VMEM<->VREG aggregate)
    cmem_bw: float         # bytes/s scalar memory
    hbm_bytes: float       # HBM capacity
    vmem_bytes: float      # VMEM capacity per core
    cores: int             # TensorCores per chip
    ici_bw: float          # bytes/s per link
    ici_links: int         # usable links per chip (torus dimension * 2)
    dcn_bw: float          # bytes/s cross-pod (data-center network)
    # fixed per-grid-program dispatch latency (s): DMA setup, program launch
    launch_latency: float = 1.5e-6

    @property
    def flops_per_byte(self) -> float:
        return self.mxu_flops / self.hbm_bw

    @property
    def ici_chip_bw(self) -> float:
        """Aggregate ICI bandwidth per chip."""
        return self.ici_bw * self.ici_links


# Four generations — portability testbed (stand-ins for the paper's 4 GPUs).
TPU_V4 = HardwareSpec(
    name="tpu_v4", generation="v4",
    mxu_flops=275e12, vpu_flops=4.3e12, trans_flops=0.54e12,
    hbm_bw=1228e9, vmem_bw=11e12, cmem_bw=0.9e12,
    hbm_bytes=32e9, vmem_bytes=64 * 2**20, cores=2,
    ici_bw=50e9, ici_links=6, dcn_bw=6.25e9,
)
TPU_V5E = HardwareSpec(
    name="tpu_v5e", generation="v5e",
    mxu_flops=197e12, vpu_flops=3.1e12, trans_flops=0.39e12,
    hbm_bw=819e9, vmem_bw=8.5e12, cmem_bw=0.7e12,
    hbm_bytes=16e9, vmem_bytes=128 * 2**20, cores=1,
    ici_bw=50e9, ici_links=4, dcn_bw=6.25e9,
)
TPU_V5P = HardwareSpec(
    name="tpu_v5p", generation="v5p",
    mxu_flops=459e12, vpu_flops=7.2e12, trans_flops=0.9e12,
    hbm_bw=2765e9, vmem_bw=22e12, cmem_bw=1.8e12,
    hbm_bytes=95e9, vmem_bytes=112 * 2**20, cores=2,
    ici_bw=100e9, ici_links=6, dcn_bw=6.25e9,
)
TPU_V6E = HardwareSpec(
    name="tpu_v6e", generation="v6e",
    mxu_flops=918e12, vpu_flops=14.3e12, trans_flops=1.8e12,
    hbm_bw=1640e9, vmem_bw=17e12, cmem_bw=1.4e12,
    hbm_bytes=32e9, vmem_bytes=160 * 2**20, cores=1,
    ici_bw=90e9, ici_links=4, dcn_bw=6.25e9,
)

SPECS: Dict[str, HardwareSpec] = {
    s.name: s for s in (TPU_V4, TPU_V5E, TPU_V5P, TPU_V6E)
}
PORTABILITY_SET: Tuple[str, ...] = ("tpu_v4", "tpu_v5e", "tpu_v5p", "tpu_v6e")

# Production dry-run target.
PRODUCTION = TPU_V5E

# ``jax.Device.device_kind`` as each registered generation reports it.
DEVICE_KINDS: Dict[str, str] = {
    "TPU v4": "tpu_v4",
    "TPU v5 lite": "tpu_v5e",
    "TPU v5": "tpu_v5p",
    "TPU v6 lite": "tpu_v6e",
}


def spec_for_device(device) -> HardwareSpec:
    """The registered spec of a live ``jax.Device``; a kind with no spec
    is an error, never a default (its measurements would be filed under
    another chip's name)."""
    kind = device.device_kind
    if kind not in DEVICE_KINDS:
        raise KeyError(f"no hardware spec for device kind {kind!r}; "
                       f"registered: {sorted(DEVICE_KINDS)}")
    return SPECS[DEVICE_KINDS[kind]]


def _squash(name: str) -> str:
    """Alphanumeric-only lowercase form used for drift-tolerant matching."""
    return re.sub(r"[^a-z0-9]", "", str(name).lower())


_NORMALIZE_CACHE: Dict[str, str] = {}


def normalize_name(name: str) -> str:
    """Canonical hardware-name string, stable under naming drift.

    Resolves to a registered spec's name whenever the alphanumeric forms
    match ("TPUv4", "tpu-v4", "TPU_V4" → "tpu_v4"); otherwise returns a
    lower_snake_case normalization of the given name, so even unregistered
    hardware gets a deterministic identity.  Memoized: the service hot
    path normalizes the same few names on every request, and the regex
    work shows up in profiles.
    """
    cached = _NORMALIZE_CACHE.get(name) if isinstance(name, str) else None
    if cached is not None:
        return cached
    sq = _squash(name)
    norm = None
    for canon in SPECS:
        if _squash(canon) == sq:
            norm = canon
            break
    if norm is None:
        norm = re.sub(r"[^a-z0-9]+", "_",
                      str(name).strip().lower()).strip("_") or "unknown"
    if isinstance(name, str) and len(_NORMALIZE_CACHE) < 4096:
        _NORMALIZE_CACHE[name] = norm
    return norm


def get(name: str) -> HardwareSpec:
    """Spec by name, tolerating naming drift via ``normalize_name``.

    Raises ``KeyError`` (with the registered names) only when even the
    normalized form is unknown.
    """
    if name in SPECS:
        return SPECS[name]
    canon = normalize_name(name)
    if canon in SPECS:
        return SPECS[canon]
    raise KeyError(
        f"unknown hardware {name!r} (normalized: {canon!r}); "
        f"registered: {sorted(SPECS)}")


def fingerprint(spec: HardwareSpec) -> str:
    """Stable identity for hardware outside the registry: the normalized
    name plus the declared peak matmul throughput and HBM bandwidth — two
    machines that agree on all three are the same tuning target for the
    cost model's purposes."""
    return (f"{normalize_name(spec.name)}"
            f"-{spec.mxu_flops / 1e12:.0f}tf-{spec.hbm_bw / 1e9:.0f}gbs")


def hardware_key(hw: Union[str, HardwareSpec]) -> str:
    """Canonical ``ConfigStore`` hardware key.

    Registered hardware (by spec or any naming-drift variant of its name)
    maps to the registry name, so "tpu_v4" and "TPUv4" share store entries;
    unregistered specs fall back to their ``fingerprint`` and unregistered
    name strings to their normalized form.
    """
    if isinstance(hw, HardwareSpec):
        canon = normalize_name(hw.name)
        # normalize_name resolves to a registry name exactly when the
        # squashed forms match, so this is the registered/unregistered test
        return canon if canon in SPECS else fingerprint(hw)
    return normalize_name(hw)
