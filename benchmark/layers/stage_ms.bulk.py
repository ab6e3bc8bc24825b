"""Admit + stage: host milliseconds per unit in the program's spans
``admit.encode``, ``device.admit`` and ``device.stage``."""


def read(ctx):
    return ctx.span_ms('admit.encode', 'device.admit', 'device.stage')
