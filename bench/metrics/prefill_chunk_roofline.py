"""Share of the prompt chunks' device time that their roofline needs (%):
the ``prefill_chunk`` modules against the operations and bytes each
chunk's algorithm needs (``bench/costs``)."""

from harness import layers

MODULES = ("jit_prefill_chunk",)


def read(ctx):
    return layers.prefill_roofline(ctx, MODULES)
