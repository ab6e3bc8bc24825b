"""Sequence index (rebuild): tree nodes per unit that the program's
rebuild applies re-ordered whole (counter ``device_idx_rebuild_nodes``)."""


def read(ctx):
    return ctx.counter('device_idx_rebuild_nodes')
