"""Share of the traced window in which no operation ran on the device (%)."""

from harness import layers


def read(ctx):
    return layers.idle_share(ctx)
