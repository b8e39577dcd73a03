"""Seconds from the harness's start to the first timed request: imports
and device start-up, planning, weights and inputs, compilation (or the
compile cache's load) and warm-up."""


def reduce(ctx):
    return ctx["setup"]["setup_s"]
