"""Host time inside the program's ``serve.prefill`` spans (prefill dispatch,
cache growth and the wait for the logits), over the traced rwkv6 decode
window."""
from chipbench import program_trace_rwkv6

KIND = "serve_rwkv6"
UNIT = "%"


def read(ctx):
    r = program_trace_rwkv6.for_reduction(ctx["trace"], ctx["counts"].get("op_scopes"))
    if not r or r["window_s"] <= 0 or "serve.prefill" not in r["span_s"]:
        return None
    return 100.0 * r["span_s"]["serve.prefill"] / r["window_s"]
