"""Host seconds spent in ``plan_emitable_network(..., verify=True)``."""


def reduce(ctx):
    return ctx["setup"]["plan_s"]
