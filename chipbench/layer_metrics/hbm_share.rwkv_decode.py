"""Bytes the traced batch's decode steps must move (every non-embedding
weight once a step, the WKV state and the token-shift carries read and
written), over the traced window, as a share of the chip's peak HBM
bandwidth."""
KIND = "serve_rwkv6"
UNIT = "%"


def read(ctx):
    t, c = ctx["trace"], ctx["counts"]
    if not t or t["window_s"] <= 0 or not c.get("decode_bytes"):
        return None
    return 100.0 * c["decode_bytes"] / t["window_s"] / ctx["peaks"]["hbm_bytes_per_s"]
