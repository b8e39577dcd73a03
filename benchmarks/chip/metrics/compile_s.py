"""Host seconds of the first ``execute_network`` call, up to its
output's ``block_until_ready``: emission, tracing, lowering, and the
compile or the compile cache's load, then one run."""


def reduce(ctx):
    return ctx["setup"]["compile_s"]
