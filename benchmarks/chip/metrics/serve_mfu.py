"""The serving window's share of the chip's roofline: for each prefill and
decode call, the larger of its operations over peak FLOP/s and its HBM bytes
over peak bytes/s (the family's counts), summed over the calls made in the
window, over the window's seconds."""


def read(ctx):
    if ctx.get("kind") != "serve":
        return None
    fam, t, c, pk = ctx["family"], ctx["traffic"], ctx["config"], ctx["peaks"]
    bound = lambda fb: max(fb[0] / pk["bf16_flops_per_s"], fb[1] / pk["hbm_bytes_per_s"])
    least = (ctx["calls"]["prefill"] * bound(fam.prefill_cost(c, t["batch"], t["prompt"]))
             + ctx["calls"]["decode"] * bound(fam.decode_cost(c, t["batch"])))
    if least <= 0:
        return None
    return 100.0 * least / ctx["window_s"]
