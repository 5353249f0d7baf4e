"""Host time of ``run_op`` outside ``solve`` (state building, result
extraction) per call: the ``iwpp.run_op`` span less its ``iwpp.solve``
child, mean over the window's calls, in ms."""

import program_spans


def read(ctx):
    return program_spans.ms_per_call(ctx.stats, ("iwpp.run_op",),
                                     less=("iwpp.solve",))
