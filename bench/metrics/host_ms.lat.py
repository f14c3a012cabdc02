"""host_ms.lat: the median host-code phase of the traced window's
requests: from a request's start (its `bench.device_put` span) to the end
of its `bench.dispatch` span, in milliseconds (`rawtrace.request_phases`)."""
from bench import rawtrace


def read(ctx):
    return rawtrace.phase_ms(ctx, "host")
