"""GPQ Pallas kernels' share of their roofline in the decode program."""

from perfbench import readers


def read(ctx):
    return readers.gpq_roofline(ctx, "decode")
