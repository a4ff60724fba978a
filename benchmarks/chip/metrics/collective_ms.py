"""Device time of collective operations per training step (synchronous
collective ops and the start-to-done spans of asynchronous ones, their
union), from the trace, averaged over the chips."""
from chipbench import reduce


def read(ctx):
    rec = ctx.get("trace")
    if rec is None or not ctx.get("steps"):
        return None
    c = reduce.comm(rec)
    if c is None or c["collective_s"] <= 0:
        return None
    return 1e3 * c["collective_s"] / ctx["steps"]
