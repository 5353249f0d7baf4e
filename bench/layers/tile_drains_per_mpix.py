"""Tile drains of the window's solves per megapixel solved
(``SolveStats.tiles_processed``; 0 where the engine drains no tiles)."""


def read(ctx):
    if not ctx.stats or ctx.mpix <= 0:
        return None
    return sum(s.tiles_processed for s in ctx.stats) / ctx.mpix
