"""GPQ Pallas kernel device time per prefill program execution."""

from perfbench import readers


def read(ctx):
    return readers.gpq_ms(ctx, "prefill")
