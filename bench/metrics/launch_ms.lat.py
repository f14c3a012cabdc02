"""launch_ms.lat: the median launch phase of the traced window's requests:
from the end of a request's `bench.dispatch` span to the start of its
program run on the device, on the host's clock with the device's events
aligned to it, in milliseconds (`rawtrace.request_phases`)."""
from bench import rawtrace


def read(ctx):
    return rawtrace.phase_ms(ctx, "launch")
