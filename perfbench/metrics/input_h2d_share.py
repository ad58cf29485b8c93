"""Time the producer spent handing the gathered rows to the device
(``input.h2d``: ``nd_array(...)``, whose ``device_put`` changes the layout
on the host; a child of ``input.fetch``) over the window, in percent."""
from perfbench import scopes


def read(ctx):
    return scopes.span_share(ctx, "input.h2d")
