"""Outer rounds per solve (``SolveStats.rounds``), mean over the window."""


def read(ctx):
    if not ctx.stats:
        return None
    return sum(s.rounds for s in ctx.stats) / len(ctx.stats)
