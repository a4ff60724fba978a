"""Device milliseconds per training step in the program's ``optimizer`` scope:
the optimizer: clipping and the AdamW update; the union of its ops'
intervals per chip, averaged over the chips."""
from chipbench import program


def read(ctx):
    return program.train_ms(ctx, "optimizer")
