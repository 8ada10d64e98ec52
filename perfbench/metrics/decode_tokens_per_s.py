"""Greedy tokens generated per second over the whole window."""

from perfbench import readers


def read(ctx):
    return readers.rate(ctx)
