"""Device microseconds per image in the traced window outside the conv
kernels: busy time less conv kernel time, over the network executions
the trace holds.  That is the joins, ReLUs, pools, pads and relayouts
(weight transposes, channel padding) between the kernels."""


def reduce(ctx):
    t = ctx["trace"]
    if t is None or not t["images"] or not t["busy_s"]:
        return None
    return (t["busy_s"] - t["conv_s"]) / t["images"] * 1e6
