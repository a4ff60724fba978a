"""Mean host milliseconds per token of the program's ``serve.sample`` span:
the sampling key's split and the dispatch of the sampling program."""
from chipbench import program


def read(ctx):
    if ctx.get("kind") != "serve":
        return None
    return program.host_ms(ctx, "serve.sample")
