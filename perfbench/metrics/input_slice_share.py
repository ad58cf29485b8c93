"""Time the producer spent gathering a batch's rows on the host
(``input.slice``, a child of ``input.fetch``) over the window, in percent."""
from perfbench import scopes


def read(ctx):
    return scopes.span_share(ctx, "input.slice")
