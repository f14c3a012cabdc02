"""idle_share.lat: the device's idle share in the latency cells:
1 - the union of device-operation intervals / the traced window."""
from bench import trace


def read(ctx):
    return 100.0 * (1.0 - trace.busy_seconds(ctx.trace) / ctx.window_s)
