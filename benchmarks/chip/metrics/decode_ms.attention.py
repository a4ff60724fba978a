"""Device milliseconds per decode program run in the program's ``attention``
scope: a hybrid's shared blocks up to their output projection (the concat
and its norm, q/k/v/o, rotary, the cache write and the attention over it)."""
from chipbench import program


def read(ctx):
    return program.decode_ms(ctx, "attention")
