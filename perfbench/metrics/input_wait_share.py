"""Share of the window that the ``fit`` loop spent inside ``next()`` of the
benchmark's own iterator wrapper (host clock). About 0 where the feed is
resident: that is the bypass."""


def read(ctx):
    feed = ctx["feed"]
    if not feed["batches"]:
        return None
    return 100.0 * feed["wait_s"] / feed["window_s"]
