"""Device microseconds of the conv kernels per image in the traced
window: every conv kernel event's duration, summed, over the network
executions the trace holds."""


def reduce(ctx):
    t = ctx["trace"]
    if t is None or not t["images"] or not t["conv_s"]:
        return None
    return t["conv_s"] / t["images"] * 1e6
