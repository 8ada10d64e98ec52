"""Decode cell: device-idle time per generate call inside the program's
own call span."""

from perfbench import scopes


def read(ctx):
    return scopes.host_idle_ms(ctx)
