"""Device time of one jitted ResNet forward."""

from perfbench import readers


def read(ctx):
    return readers.program_ms(ctx, "forward")
