"""return_ms.lat: the median return phase of the traced window's
requests: from the end of a request's program run to the end of its
`bench.readback` span (done signal, copy to the host, conversion), on the
host's clock with the device's events aligned to it, in milliseconds
(`rawtrace.request_phases`)."""
from bench import rawtrace


def read(ctx):
    return rawtrace.phase_ms(ctx, "return")
