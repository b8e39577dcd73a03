"""Images completed in the window over the window's seconds."""


def reduce(ctx):
    w = ctx["window"]
    if not w["completed"]:
        return None
    return w["completed"] / w["window_s"]
