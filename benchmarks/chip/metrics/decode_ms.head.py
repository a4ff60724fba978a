"""Device milliseconds per decode program run in the program's ``head`` scope:
the head (unembedding to the next-token logits)."""
from chipbench import program


def read(ctx):
    return program.decode_ms(ctx, "head")
