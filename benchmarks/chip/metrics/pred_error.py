"""How far the planner's Def-3 model shares time among layers unlike
the chip: the mean over layers of |r_l / r - 1|, where r_l is layer l's
measured kernel time over its Def-3 gross duration and r is the sum of
measured times over the sum of durations.  0 means the model splits the
time as the chip does; it needs no fitted cycle time."""


def reduce(ctx):
    t = ctx["trace"]
    if t is None or not t["layer_s"]:
        return None
    measured, predicted = t["layer_s"], ctx["predicted"]
    if len(measured) != len(predicted) or not sum(measured):
        return None
    r = sum(measured) / sum(predicted)
    return sum(abs(m / p / r - 1) for m, p in zip(measured, predicted)) \
        / len(measured)
