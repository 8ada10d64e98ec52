"""GPQ Pallas kernel device time per decode program execution."""

from perfbench import readers


def read(ctx):
    return readers.gpq_ms(ctx, "decode")
