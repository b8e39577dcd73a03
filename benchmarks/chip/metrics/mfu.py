"""The whole forward pass's share of the chip's peak, in percent: conv
operations per image times images per second in the window, over the
bf16 peak.  Float32 at full precision runs as several bf16 passes, so
the share cannot pass 100."""
import counts


def reduce(ctx):
    w = ctx["window"]
    if not w["completed"]:
        return None
    rate = w["completed"] / w["window_s"]
    return (counts.network_flops(ctx["cfg"]) * rate
            / ctx["peak"]["bf16_flops_per_s"] * 100)
