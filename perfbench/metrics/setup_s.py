"""Seconds from process start to the end of set-up: device init, weights and inputs from the seed, plans, compiles or cache loads, warm-up."""

from perfbench import readers


def read(ctx):
    return ctx.setup_s
