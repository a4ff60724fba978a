"""The serving window's share of the chip's roofline, as ``serve_mfu``
defines it, with each decode call counted at the cache length it attends
over: ``decode_cost(config, batch, cache_len)``.  A batch's decode calls
attend over prompt + 1, prompt + 2, ... positions in turn.  Read in traced
runs; None for a family whose decode cost takes no cache length."""
import inspect


def read(ctx):
    if ctx.get("kind") != "serve" or ctx.get("trace") is None:
        return None
    fam, t, c, pk = ctx["family"], ctx["traffic"], ctx["config"], ctx["peaks"]
    if "cache_len" not in inspect.signature(fam.decode_cost).parameters:
        return None
    bound = lambda fb: max(fb[0] / pk["bf16_flops_per_s"], fb[1] / pk["hbm_bytes_per_s"])
    B, P, G = t["batch"], t["prompt"], t["new_tokens"]
    least = ctx["calls"]["prefill"] * bound(fam.prefill_cost(c, B, P))
    least += sum(bound(fam.decode_cost(c, B, P + i % G + 1))
                 for i in range(ctx["calls"]["decode"]))
    if least <= 0:
        return None
    return 100.0 * least / ctx["window_s"]
