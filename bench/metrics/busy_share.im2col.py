"""busy_share.im2col: the share of the device's busy time that econv's
im2col takes: the device time of the operations whose op_name holds an
`im2col` scope (patch extraction and the patch matrix's layout up to the
event-matmul kernel's operand), over the union of device-operation
intervals in the traced window. Op_names come from the program's HLO in
the trace file (`rawtrace.of`); a program without the scope reads None."""
from bench import rawtrace, trace


def read(ctx):
    raw = rawtrace.of(ctx)
    if raw is None:
        return None
    im2col = rawtrace.scoped_seconds(ctx.trace, raw.names, "im2col")
    busy = trace.busy_seconds(ctx.trace)
    if im2col <= 0 or busy <= 0:
        return None
    return 100.0 * im2col / busy
