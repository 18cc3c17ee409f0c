"""Roofline share of the WKV recurrence: the least time the chip could take
for the traced batch's decode steps' recurrence (the larger of its state
bytes, read and written once a step, over peak HBM bandwidth and of its
operations over the bf16 peak; the bytes bound it) over the device self
time of the ``wkv`` scope.  Only what the recurrence must move counts: an
update that the compiler fuses into ops of another scope makes this read
high."""
from chipbench import program_trace_rwkv6

KIND = "serve_rwkv6"
UNIT = "%"


def read(ctx):
    r = program_trace_rwkv6.for_reduction(ctx["trace"], ctx["counts"].get("op_scopes"))
    c, peaks = ctx["counts"], ctx["peaks"]
    if not r or not r["scope_s"].get("wkv") or not c.get("wkv_state_bytes"):
        return None
    least = max(c["wkv_state_bytes"] / peaks["hbm_bytes_per_s"],
                c["wkv_flops"] / peaks["bf16_flops_per_s"])
    return 100.0 * least / r["scope_s"]["wkv"]
