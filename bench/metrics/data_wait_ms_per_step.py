"""Host time per window step that the training loop waited for its next
batch from the prefetcher (the program's ``data_wait`` spans, from its
trace of the window)."""

import program_trace


def read(ctx):
    return program_trace.ms_per_step(ctx, "data_wait")
