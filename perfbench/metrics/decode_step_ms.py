"""Device time of one jitted decode step."""

from perfbench import readers


def read(ctx):
    return readers.program_ms(ctx, "decode")
