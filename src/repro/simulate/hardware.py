"""TPU hardware generations (the paper's H-axis, adapted per DESIGN §3).

The paper's cross-hardware pair (H100 NVL vs A100 PCIe) maps onto
v5p-class (pricier, faster, higher-bandwidth) vs v5e (cheaper, slower) —
same structure: the load-driven cost spread must reproduce with compressed
magnitude on the cheaper part. fp8 is native on the v6e-class entry only;
v5e runs fp8 through a dequant-emulation path (int8 is native everywhere),
reproducing the paper's hardware-conditional quantization caveat. The
`paper_crosshw` experiment plan (ISSUE 3) spans all three generations in
one store, and `experiments.analyze.fp8_inversion` conditions the uplift
table on `native_fp8` — the dense inversion must vanish on v6e.

Prices are public on-demand list prices (us-central, mid-2026 era); the
framework treats them as a replaceable price book, exactly as the paper
treats Azure rates ("the framework's value is in the methodology, not
specific dollar amounts", §6.9).
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class HardwareGen:
    name: str
    peak_flops_bf16: float      # FLOP/s per chip
    peak_flops_int8: float
    hbm_bw: float               # bytes/s per chip
    ici_bw: float               # bytes/s per link (one direction)
    hbm_bytes: float            # per chip
    price_per_chip_hr: float    # $/chip-hour on-demand
    native_fp8: bool
    native_int8: bool = True

    def peak(self, quant: str) -> float:
        if quant == "int8" and self.native_int8:
            return self.peak_flops_int8
        if quant == "fp8" and self.native_fp8:
            return self.peak_flops_int8          # fp8 rides the 2x MXU path
        return self.peak_flops_bf16


V5E = HardwareGen("tpu-v5e", 197e12, 394e12, 819e9, 50e9, 16e9, 1.20,
                  native_fp8=False)
V5P = HardwareGen("tpu-v5p", 459e12, 918e12, 2765e9, 100e9, 95e9, 4.20,
                  native_fp8=False)
V6E = HardwareGen("tpu-v6e", 918e12, 1836e12, 1640e9, 100e9, 32e9, 2.70,
                  native_fp8=True)

HW_BY_NAME = {h.name: h for h in (V5E, V5P, V6E)}

# `jax.devices()[0].device_kind` -> generation: the live tier prices its
# run by the chip it ran on ("TPU v5 lite" is what a v5e reports).
HW_BY_DEVICE_KIND = {"TPU v5 lite": V5E, "TPU v5": V5P, "TPU v6 lite": V6E}


def hw_for_device_kind(kind: str) -> HardwareGen:
    """The generation (and price) of a device; an unknown kind raises."""
    if kind not in HW_BY_DEVICE_KIND:
        raise KeyError(f"no hardware entry for device kind {kind!r}; "
                       f"known: {sorted(HW_BY_DEVICE_KIND)}")
    return HW_BY_DEVICE_KIND[kind]

# Pseudo-hardware entry for the CPU real-execution tier: throughput is
# measured, only the price matters for C_eff shape validation.
CPU_NODE = HardwareGen("cpu-node", 1e12, 1e12, 5e10, 1e9, 64e9, 1.00,
                       native_fp8=False, native_int8=False)
HW_BY_NAME["cpu-node"] = CPU_NODE
