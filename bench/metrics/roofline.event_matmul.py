"""roofline.event_matmul: the event-matmul kernels' share of their roofline.

Sum over the window's calls of the least time (`work.least_seconds` of
2 x operand non-zeros x N operations and the operand at its bits, weights
and output bytes) / the device time of the CSR-of-tiles spike-matmul
kernels that `econv` and `spike_matmul` run (their custom calls carry the
name of the jitted wrapper, `_spike_matmul_csr_core`). The non-zeros come
from the reference's spike maps of the same images.
"""
from bench import trace, work

NEEDS_OPERANDS = True
FAMILY = "event_matmul"
PATTERNS = (r"^%_spike_matmul\w*\.\d+ = .*tpu_custom_call",)


def read(ctx):
    device_s = trace.family_seconds(ctx.trace, PATTERNS)
    least = sum(work.least_seconds(ops, nbytes,
                                   ctx.peaks["bf16_flops_per_s"],
                                   ctx.peaks["hbm_bytes_per_s"])
                for family, ops, nbytes in ctx.calls if family == FAMILY)
    if device_s <= 0 or least <= 0:
        return None
    return 100.0 * least / device_s
