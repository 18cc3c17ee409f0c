"""Share of the traced rwkv6 decode window in which no op ran on the device."""
KIND = "serve_rwkv6"
UNIT = "%"


def read(ctx):
    t = ctx["trace"]
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
