"""Time in all-gather, reduce-scatter, all-reduce and other collective ops
during which no other op ran on that chip, over the traced window, averaged
over the chips."""
KIND = "train"
UNIT = "%"


def read(ctx):
    t = ctx["trace"]
    if not t or t["window_s"] <= 0 or t["devices"] < 2:
        return None
    return 100.0 * t["exposed_collective_s"] / t["window_s"]
