"""roofline.lif: the LIF kernels' share of their (memory) roofline.

Sum over the window's LIF calls of the least bytes (`work.lif_work`: drive
read in float32, spikes at 1 bit, occupancy maps) / peak HBM bandwidth,
over the device time of the `lif_scan` and `lif_scan_occ` kernels (custom
calls named after their jitted wrappers, `lif` and `lif_occ`).
"""
from bench import trace, work

NEEDS_OPERANDS = False
FAMILY = "lif"
PATTERNS = (r"^%lif\w*\.\d+ = .*tpu_custom_call",)


def read(ctx):
    device_s = trace.family_seconds(ctx.trace, PATTERNS)
    least = sum(work.least_seconds(ops, nbytes,
                                   ctx.peaks["bf16_flops_per_s"],
                                   ctx.peaks["hbm_bytes_per_s"])
                for family, ops, nbytes in ctx.calls if family == FAMILY)
    if device_s <= 0 or least <= 0:
        return None
    return 100.0 * least / device_s
