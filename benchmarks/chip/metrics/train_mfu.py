"""Model FLOP/s utilization of a training window: the operations one token
needs (the configuration's family counts them, without recomputation) times
tokens per second per chip, over the chip's peak bf16 rate."""


def read(ctx):
    if ctx.get("kind") != "train" or not ctx.get("steps"):
        return None
    fam, t = ctx["family"], ctx["traffic"]
    flops = fam.train_flops_per_token(ctx["config"], t["seq"])
    return 100.0 * ctx["tokens_per_s_per_chip"] * flops / ctx["peaks"]["bf16_flops_per_s"]
