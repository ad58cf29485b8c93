"""Time the input pipeline's producer thread spent fetching
(``input.fetch``: ``PrefetchingIter._produce`` around ``source.next()``)
over the window, in percent: the input layer's time busy. About 100 says the
producer is saturated and a deeper queue buys nothing; about 50 beside a
waiting ``fit`` says its transfer and the step take turns."""
from perfbench import scopes


def read(ctx):
    return scopes.span_share(ctx, "input.fetch")
