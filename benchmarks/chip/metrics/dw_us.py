"""Device microseconds per image of the depthwise conv kernels in the
traced window: the per-layer kernel time (``layer_s``) at the
configuration's ``groups > 1`` layers, summed, over the network
executions the trace holds."""
import dw_counts


def reduce(ctx):
    s = dw_counts.dw_seconds(ctx["cfg"], ctx["trace"])
    return None if s is None else s * 1e6
