"""Nominal model FLOPs over the traced window as a percent of the bf16 peak (decode cell)."""

from perfbench import readers


def read(ctx):
    return readers.mfu(ctx)
