"""Device time of the operations scoped ``transpose(jvp(RNN/...`` (the
backward of the fused RNN op: its scan's transpose with the cell's backward
in it, and the projections' gradients) over the step programs' device time
on the busiest chip, in percent."""
from perfbench import scopes


def read(ctx):
    return scopes.share_of_step(
        ctx, lambda scope, back: back
        and scopes.op_type(scope) == "RNN") or None
