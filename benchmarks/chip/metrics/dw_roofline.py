"""Share of the depthwise conv kernels' roofline, in percent: the least
time the chip could take for one image's depthwise layers
(``dw_counts.dw_least_seconds``, the larger of operations over peak
FLOP/s and exact bytes over HBM bandwidth, layer by layer) over their
measured time per image."""
import dw_counts


def reduce(ctx):
    s = dw_counts.dw_seconds(ctx["cfg"], ctx["trace"])
    if not s:
        return None
    return dw_counts.dw_least_seconds(ctx["cfg"], ctx["peak"]) / s * 100
