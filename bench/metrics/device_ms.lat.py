"""device_ms.lat: the median device phase of the traced window's
requests: a request's program run on the device, from its start to its
end, in milliseconds (`rawtrace.request_phases`)."""
from bench import rawtrace


def read(ctx):
    return rawtrace.phase_ms(ctx, "device")
