"""Programs the program built (backend compiles and persistent-cache loads,
by its own counter) while the serve window's profile was recorded."""
from chipbench import program


def read(ctx):
    if ctx.get("kind") != "serve":
        return None
    return program.compiles_in_window(ctx)
