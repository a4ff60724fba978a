"""The part of `train_ms.exchange` during which no op outside the program's
``exchange`` scope runs on that chip, milliseconds per training step: the
exchange the step waits for."""
from chipbench import program


def read(ctx):
    return program.train_ms(ctx, "exposed.exchange")
