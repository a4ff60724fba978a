"""Mean device idle time between consecutive decode programs, from the
trace's program (module) events: the host's round trip per token."""
from chipbench import reduce


def read(ctx):
    rec = ctx.get("trace")
    if rec is None or ctx.get("kind") != "serve":
        return None
    g = reduce.module_gaps(rec, "decode")
    return None if g is None else 1e3 * g
