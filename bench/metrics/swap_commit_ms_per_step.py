"""Host time per window step of the banked boundary's commit of a
prediction hit (the program's ``swap_commit`` spans inside ``swap``, from
its trace of the window)."""

import program_trace


def read(ctx):
    return program_trace.ms_per_step(ctx, "swap_commit")
