"""Device time of one fused decode step (ms): the time of the ``loop``
modules (the engine's K-step decode scan) over count x K."""

from harness import layers

MODULES = ("jit_loop",)


def read(ctx):
    return layers.decode_step_ms(ctx, MODULES)
