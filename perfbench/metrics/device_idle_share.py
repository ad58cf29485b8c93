"""1 - (union of device-operation intervals on the busiest chip) / traced
window, in percent."""


def read(ctx):
    trace = ctx["trace"]
    dev = trace.busiest()
    if dev is None:
        return None
    return 100.0 * (1.0 - trace.busy_seconds()[dev] / ctx["window_s"])
