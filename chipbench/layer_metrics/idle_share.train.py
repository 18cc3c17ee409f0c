"""Share of the traced train window in which no op ran on a chip, averaged
over the chips."""
KIND = "train"
UNIT = "%"


def read(ctx):
    t = ctx["trace"]
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
