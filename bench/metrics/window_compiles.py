"""jit dispatch: XLA executables built (compiled, or read from the
persistent cache) inside the measured window."""


def read(ctx):
    return float(ctx.window_compiles)
