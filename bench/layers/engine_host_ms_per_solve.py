"""Host time of the engine per call outside its waits for the device: the
``iwpp.engine`` span less its ``iwpp.engine.wait`` children, mean over the
window's calls, in ms."""

import program_spans


def read(ctx):
    return program_spans.ms_per_call(ctx.stats, ("iwpp.engine",),
                                     less=("iwpp.engine.wait",))
