"""Host time of engine selection per call: the ``iwpp.select`` (input
probes, ranking) and ``iwpp.calibrate`` spans, mean over the window's
calls, in ms."""

import program_spans


def read(ctx):
    return program_spans.ms_per_call(ctx.stats,
                                     ("iwpp.select", "iwpp.calibrate"))
