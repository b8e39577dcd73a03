"""Share of the traced window, in percent, in which no operation ran on
the device: 1 - busy / window."""


def reduce(ctx):
    t = ctx["trace"]
    if t is None or not t["busy_s"]:
        return None
    return (1 - t["busy_s"] / t["window_s"]) * 100
