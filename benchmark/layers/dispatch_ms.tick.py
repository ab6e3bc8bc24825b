"""Dispatch: host milliseconds per unit in the program's span
``device.dispatch`` (enqueueing the fused general programs)."""


def read(ctx):
    return ctx.span_ms('device.dispatch')
