"""Device: the share of the traced stretch in which no op ran on the
device (1 - union of device-op intervals / traced window)."""


def read(ctx):
    t = ctx["trace"]
    if not t or t["busy_s"] <= 0:
        return None
    return (1.0 - t["busy_s"] / t["window_s"]) * 100.0
