"""Device self time of the ops in none of the rwkv model's named scopes,
over the traced window: in the decode step, the layer loop's slicing of the
stacked WKV state and weights, the state's relayout copies, its write into
the stacked output and the whole-state copy after the loop."""
from chipbench import program_trace, program_trace_rwkv6

KIND = "serve_rwkv6"
UNIT = "%"


def read(ctx):
    r = program_trace_rwkv6.for_reduction(ctx["trace"], ctx["counts"].get("op_scopes"))
    if not r or r["window_s"] <= 0 or program_trace.UNSCOPED not in r["scope_s"]:
        return None
    return 100.0 * r["scope_s"][program_trace.UNSCOPED] / r["window_s"]
