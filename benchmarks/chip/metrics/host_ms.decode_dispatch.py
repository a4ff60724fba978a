"""Mean host milliseconds per token of the program's ``serve.decode`` span:
the position array and the dispatch of the decode program."""
from chipbench import program


def read(ctx):
    if ctx.get("kind") != "serve":
        return None
    return program.host_ms(ctx, "serve.decode")
