"""Device milliseconds per training step in the program's ``exchange`` scope:
the explicit-DP gradient exchange (bucket pack, codec, collectives, unpack),
its asynchronous collective spans included; the union of its ops' intervals
per chip, averaged over the chips."""
from chipbench import program


def read(ctx):
    return program.train_ms(ctx, "exchange")
