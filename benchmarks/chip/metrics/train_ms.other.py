"""Device milliseconds per training step in which an op in no named scope of
the program runs (busy time less the scoped ops' union), averaged over the
chips."""
from chipbench import program


def read(ctx):
    return program.train_ms(ctx, program.OTHER)
