"""The operations the traced steps require (6 x non-embedding parameters plus
the WKV recurrence, per token; recomputation does not count), over the
traced window, as a share of the bf16 peak of all the cell's chips."""
from chipbench import counts

KIND = "train"
UNIT = "%"


def read(ctx):
    t, c = ctx["trace"], ctx["counts"]
    if not t or t["window_s"] <= 0 or not c.get("traced_tokens"):
        return None
    flops = counts.rwkv6_train_flops_per_token(ctx["model"]) * c["traced_tokens"]
    peak = ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"]
    return 100.0 * flops / t["window_s"] / peak
