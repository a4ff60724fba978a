"""Device milliseconds per prefill program run in the program's ``attention``
scope (for a hybrid: the shared blocks' projections, rotary, cache writes
and the fused causal kernel)."""
from chipbench import program


def read(ctx):
    if ctx.get("kind") != "serve":
        return None
    prog = program.programs(ctx)
    if prog is None or "serve.prefill" not in prog["modules"]:
        return None
    sec = program._cached(ctx, "prefill_s", lambda: program.scope_seconds(
        ctx["trace"], prog["scopes"], prog["names"], prog["modules"]["serve.prefill"]))
    return 1e3 * sec["attention"] / sec["runs"] if sec.get("runs") else None
