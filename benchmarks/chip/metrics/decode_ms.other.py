"""Device milliseconds per decode program run in which an op in no named scope
of the program runs (the layer scan's own work, the final norm)."""
from chipbench import program


def read(ctx):
    return program.decode_ms(ctx, program.OTHER)
