"""Device milliseconds per training step in the program's ``mlp`` scope: the
MLP blocks, forward and backward; the union of its ops' intervals per chip,
averaged over the chips."""
from chipbench import program


def read(ctx):
    return program.train_ms(ctx, "mlp")
