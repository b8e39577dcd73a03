"""Mean latency of a request, in milliseconds, over the whole window:
the window's seconds over the requests completed in it.  With one
request in flight that is the time from one request's call to the
next's, its wait included."""


def reduce(ctx):
    w = ctx["window"]
    if not w["completed"]:
        return None
    return w["window_s"] / w["completed"] * 1e3
