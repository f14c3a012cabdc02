"""mfu: the whole step's share of the chip's bf16 peak.

Dense-equivalent FLOPs per image (the configuration's
`dense_flops_per_image`) x images completed in the traced window / the
window / the bf16 peak of `devices.json`.
"""


def read(ctx):
    if ctx.images == 0:
        return None
    return 100.0 * ctx.dense_flops_per_image * ctx.images \
        / ctx.window_s / ctx.peaks["bf16_flops_per_s"]
