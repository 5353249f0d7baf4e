"""Device -> host reads per call (``SolveStats.host_syncs``), mean over the
window's calls."""

import program_spans


def read(ctx):
    return program_spans.syncs_per_call(ctx.stats)
