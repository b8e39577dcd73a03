"""Mean host microseconds of the program's ``executor.emit`` span per
``execute_network`` call in the traced window: reading back the plan's
emitted layer kernels (emitting them, on a plan's first call).  None
where the trace holds no such span."""
import spans


def reduce(ctx):
    return spans.mean_us(ctx["spans"], "executor.emit")
