"""2 x non-embedding parameters x output tokens of the traced batch, over
the traced window, as a share of the chip's bf16 peak."""
from chipbench import counts

KIND = "serve"
UNIT = "%"


def read(ctx):
    t, c = ctx["trace"], ctx["counts"]
    if not t or t["window_s"] <= 0 or not c.get("output_tokens"):
        return None
    flops = counts.dense_decode_flops_per_token(ctx["model"]) * c["output_tokens"]
    return 100.0 * flops / t["window_s"] / ctx["peaks"]["bf16_flops_per_s"]
