"""Device milliseconds per training step in the program's ``grad_accum`` scope:
the microbatch gradient accumulation; the union of its ops' intervals per
chip, averaged over the chips."""
from chipbench import program


def read(ctx):
    return program.train_ms(ctx, "grad_accum")
