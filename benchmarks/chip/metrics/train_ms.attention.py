"""Device milliseconds per training step in the program's ``attention`` scope:
the attention blocks (projections, rotary, blockwise attention), forward and
backward; the union of its ops' intervals per chip, averaged over the chips."""
from chipbench import program


def read(ctx):
    return program.train_ms(ctx, "attention")
