"""Hardware constants for the planner, simulator and roofline analysis."""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class HWSpec:
    name: str
    peak_flops: float          # /s (bf16 where applicable)
    fast_bw: float             # fast-tier bandwidth, B/s
    slow_bw: float             # slow-tier bandwidth, B/s
    mig_bw: float              # migration bandwidth fast<->slow, B/s (per dir)
    fast_bytes: float          # fast-tier capacity (per device)
    link_bw: float = 0.0       # interconnect per link, B/s (roofline)
    mig_overhead: float = 0.0  # per-migration fixed critical-path cost, s
                               # (move_pages syscall / TLB shootdown on CPU;
                               #  DMA descriptor dispatch on TPU)


# TPU v5e chip: HBM is the fast tier; host DRAM over PCIe is the slow tier.
TPU_V5E = HWSpec(
    name="tpu-v5e",
    peak_flops=197e12,
    fast_bw=819e9,
    slow_bw=32e9,              # host DRAM as seen from device, PCIe-bound
    mig_bw=16e9,               # PCIe gen4 x16 per direction (effective)
    fast_bytes=16e9,
    link_bw=50e9,              # ICI per link
    mig_overhead=5e-6,
)

def default_cost_model():
    """The default machine as a time-domain ``CostModel``: TPU_V5E's
    constants plus the host-side split the byte-domain ``HWSpec`` cannot
    express.  One shared instance prices the planner (``runtime.plan``),
    the benchmarks, and the roofline table (``benchmarks/roofline.py``) —
    the single source of truth for the default machine's numbers.

    Imported lazily: ``repro.runtime.costmodel`` depends on this module
    for the raw constants."""
    from repro.runtime.costmodel import TPU_V5E_COST
    return TPU_V5E_COST


# The paper's evaluation platform (Table 2): 2-socket Xeon, local vs remote DDR4.
PAPER_HM = HWSpec(
    name="paper-xeon-hm",
    peak_flops=1.5e12,         # ~2x12-core AVX2 Xeon E5-2670v3 fp32
    fast_bw=34e9,
    slow_bw=19e9,
    mig_bw=19e9,               # cross-socket
    fast_bytes=6.4e9,          # Fig.10 uses 20% of peak model footprint
    mig_overhead=2e-6,         # per page, amortized over batched move_pages
                               # with 4-thread parallel copy (Yan et al. mech.)
)


# The hardware description for each accelerator this repo runs on, keyed by
# ``jax.devices()[0].device_kind``.  A device that is not listed is an error:
# planning a chip with another chip's numbers would be silently wrong.
DEVICE_KINDS = {
    "TPU v5 lite": TPU_V5E,
    "TPU v5e": TPU_V5E,
}


def for_device_kind(kind: str) -> HWSpec:
    """The ``HWSpec`` the planner uses on a device of this ``device_kind``."""
    try:
        return DEVICE_KINDS[kind]
    except KeyError:
        raise ValueError(f"no hardware description for device kind {kind!r} "
                         f"(known: {sorted(DEVICE_KINDS)})") from None
