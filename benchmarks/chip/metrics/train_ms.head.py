"""Device milliseconds per training step in the program's ``head`` scope: the
tied head: unembedding and cross-entropy, forward and backward; the union of
its ops' intervals per chip, averaged over the chips."""
from chipbench import program


def read(ctx):
    return program.train_ms(ctx, "head")
