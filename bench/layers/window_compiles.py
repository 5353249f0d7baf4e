"""Programs compiled or loaded from the compile cache inside the window."""


def read(ctx):
    return float(ctx.compiles)
