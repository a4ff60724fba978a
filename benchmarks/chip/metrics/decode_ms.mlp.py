"""Device milliseconds per decode program run in the program's ``mlp`` scope:
a hybrid's shared blocks' norm and gated MLP, less their adapters."""
from chipbench import program


def read(ctx):
    return program.decode_ms(ctx, "mlp")
