"""Kernels: device milliseconds per unit of the fused general programs
(``_fused_general_packed``, ``_wide``, ``_incr``), from the trace."""


def read(ctx):
    return ctx.program_ms(r'fused_general')
