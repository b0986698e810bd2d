"""Share of the fused decode loops' device time that the roofline of
their active rows' steps needs (%), from ``bench/costs``."""

from harness import layers

MODULES = ("jit_loop",)


def read(ctx):
    return layers.decode_roofline(ctx, MODULES)
