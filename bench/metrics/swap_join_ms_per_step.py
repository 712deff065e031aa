"""Host time per window step that the training thread spent joining the
swap planner's background job (the program's ``swap_join`` spans inside
``swap``, from its trace of the window)."""

import program_trace


def read(ctx):
    return program_trace.ms_per_step(ctx, "swap_join")
