"""The part of `collective_ms` during which no other operation runs on that
chip, per training step: the measured counterpart of exposed communication."""
from chipbench import reduce


def read(ctx):
    rec = ctx.get("trace")
    if rec is None or not ctx.get("steps"):
        return None
    c = reduce.comm(rec)
    if c is None or c["collective_s"] <= 0:
        return None
    return 1e3 * c["exposed_s"] / ctx["steps"]
