"""Programs compiled inside the window: the step's retrace guard and JAX's
own backend-compile events, after the window minus before. Should be 0."""


def read(ctx):
    return float(sum(ctx["counters"].values()))
