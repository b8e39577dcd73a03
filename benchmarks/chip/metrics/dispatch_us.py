"""Mean host microseconds inside ``execute_network`` per request in the
window: the summed time from each call to its return, before the
output is waited for, over the requests dispatched."""


def reduce(ctx):
    w = ctx["window"]
    if not w["attempted"]:
        return None
    return w["dispatch_s"] / w["attempted"] * 1e6
