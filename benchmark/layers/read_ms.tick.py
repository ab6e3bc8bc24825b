"""Patch read + materialize: host milliseconds per unit in the
program's spans ``device.patch_read`` and ``doc_set.materialize``."""


def read(ctx):
    return ctx.span_ms('device.patch_read', 'doc_set.materialize')
