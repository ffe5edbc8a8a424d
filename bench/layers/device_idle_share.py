"""The device's idle share of the traced window, in %: 1 minus the union
of the intervals in which an operation ran on the chip (profiler trace)
over the window's length."""

from mbench import layerlib


def read(ctx):
    return layerlib.idle_share(ctx)
