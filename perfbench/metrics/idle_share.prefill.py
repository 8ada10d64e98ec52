"""Percent of the traced window with no device operation running (prefill cell)."""

from perfbench import readers


def read(ctx):
    return readers.idle_share(ctx)
