"""Device idle time inside the program's ``serve.token`` spans (one a
decode-loop iteration of ``Server.generate``), over the traced rwkv6
decode window."""
from chipbench import program_trace_rwkv6

KIND = "serve_rwkv6"
UNIT = "%"


def read(ctx):
    r = program_trace_rwkv6.for_reduction(ctx["trace"], ctx["counts"].get("op_scopes"))
    if not r or r["window_s"] <= 0 or "serve.token" not in r["idle_in_span_s"]:
        return None
    return 100.0 * r["idle_in_span_s"]["serve.token"] / r["window_s"]
