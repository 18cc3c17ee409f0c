"""Device self time of the ops in the model's ``kv_cache`` named scope (the
decode step's slices and writes of the stacked KV cache, with the copies
XLA makes for them), over the traced window."""
from chipbench import program_trace

KIND = "serve"
UNIT = "%"


def read(ctx):
    r = program_trace.for_reduction(ctx["trace"])
    if not r or r["window_s"] <= 0 or "kv_cache" not in r["scope_s"]:
        return None
    return 100.0 * r["scope_s"]["kv_cache"] / r["window_s"]
