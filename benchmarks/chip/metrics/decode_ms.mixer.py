"""Device milliseconds per decode program run in the program's ``mixer``
scope: the Mamba2 mixer blocks (projections, convolution, state update and
read-out)."""
from chipbench import program


def read(ctx):
    return program.decode_ms(ctx, "mixer")
