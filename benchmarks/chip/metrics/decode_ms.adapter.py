"""Device milliseconds per decode program run in the program's ``adapter``
scope: each application's LoRA on the shared MLP and its output linear.
None where the program has no such scope."""
from chipbench import program


def read(ctx):
    prog = program.programs(ctx)
    if prog is None or "adapter" not in prog["names"]:
        return None
    return program.decode_ms(ctx, "adapter")
