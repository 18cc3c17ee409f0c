"""Device self time of the ops in the rwkv model's ``wkv`` named scope (the
decode step's read, bonus, decay and update of the state; the prefill's
chunk scan), over the traced window."""
from chipbench import program_trace_rwkv6

KIND = "serve_rwkv6"
UNIT = "%"


def read(ctx):
    r = program_trace_rwkv6.for_reduction(ctx["trace"], ctx["counts"].get("op_scopes"))
    if not r or r["window_s"] <= 0 or "wkv" not in r["scope_s"]:
        return None
    return 100.0 * r["scope_s"]["wkv"] / r["window_s"]
