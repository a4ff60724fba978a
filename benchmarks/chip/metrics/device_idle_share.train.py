"""Share of the traced training window in which no operation ran on the
device: 1 - (union of busy intervals / window), averaged over the chips."""
from chipbench import reduce


def read(ctx):
    rec = ctx.get("trace")
    if rec is None or ctx.get("kind") != "train":
        return None
    share = reduce.idle_share(rec)
    return None if share is None else 100.0 * share
