"""Share of the conv kernels' roofline, in percent: the least time the
chip could take for one image's conv layers (``counts.least_seconds``,
the larger of operations over peak FLOP/s and logical bytes over HBM
bandwidth, layer by layer) over their measured time per image."""
import counts


def reduce(ctx):
    t = ctx["trace"]
    if t is None or not t["images"] or not t["conv_s"]:
        return None
    least = counts.least_seconds(ctx["cfg"], ctx["peak"])
    return least / (t["conv_s"] / t["images"]) * 100
