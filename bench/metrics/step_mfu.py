"""Operations of the traced prompt and output tokens over the device's
busy time at the bf16 peak (%): the whole step's share of the peak,
whichever modules do the work."""

from harness import layers


def read(ctx):
    return layers.step_mfu(ctx)
