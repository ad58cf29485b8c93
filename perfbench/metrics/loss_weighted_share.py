"""The share of the window's tokens that carried loss, in percent: the
positions with a weight over 0 that the weighted loss counted on the device
(the program's counter ``loss.weighted_tokens``: ``TokenCrossEntropy``'s
auxiliary state, read at the window's two ends by ``Program.counters()`` into
the program's counter of that name) over the tokens of data the window
trained. Under block diffusion these are the masked positions of the noisy
copy: the mean of the noise schedule (55% for t uniform on [0.1, 1]). Nothing
to read from a program without the counter."""
from perfbench import scopes


def read(ctx):
    profiler = scopes.program_profiler(ctx)
    items = getattr(ctx["model"], "items_per_batch", None)
    if items is None or not hasattr(profiler, "counters"):
        return None
    counted = profiler.counters().get("loss.weighted_tokens")
    tokens = ctx["feed"]["batches"] * items(ctx["cfg"], ctx["traffic"])
    if not counted or not tokens:
        return None
    return 100.0 * counted / tokens
