"""Mean host microseconds of the program's ``executor.launch`` span per
``execute_network`` call in the traced window: the call of the jitted
network program, up to its return, before the output is waited for.
None where the trace holds no such span.

The profiler slows the host, so this reads above the launch of an
untraced call (at times above the untraced ``dispatch_us`` that holds
it).  It splits a traced dispatch between emission and launch; a change
to the launch is held against ``dispatch_us`` and the end-to-end
metric."""
import spans


def reduce(ctx):
    return spans.mean_us(ctx["spans"], "executor.launch")
