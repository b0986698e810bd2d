"""Device time of one prompt chunk (ms): the time of the
``prefill_chunk`` modules over their count."""

from harness import layers

MODULES = ("jit_prefill_chunk",)


def read(ctx):
    return layers.prefill_chunk_ms(ctx, MODULES)
