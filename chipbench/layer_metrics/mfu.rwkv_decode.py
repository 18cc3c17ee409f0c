"""The operations the traced batch's tokens require (2 x non-embedding
parameters and the WKV recurrence a token), over the traced window, as a
share of the chip's bf16 peak."""
from chipbench import counts_rwkv6_decode

KIND = "serve_rwkv6"
UNIT = "%"


def read(ctx):
    t, c = ctx["trace"], ctx["counts"]
    if not t or t["window_s"] <= 0 or not c.get("output_tokens"):
        return None
    flops = counts_rwkv6_decode.decode_flops_per_token(ctx["model"]) * c["output_tokens"]
    return 100.0 * flops / t["window_s"] / ctx["peaks"]["bf16_flops_per_s"]
