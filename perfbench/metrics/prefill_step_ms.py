"""Device time of one jitted prefill."""

from perfbench import readers


def read(ctx):
    return readers.program_ms(ctx, "prefill")
