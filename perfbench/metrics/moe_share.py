"""Device time of the operations scoped ``MoEFFN/`` (route, dispatch, the
held experts, the shared expert, combine; forward, recomputed and backward)
over the step programs' device time on the busiest chip, in percent."""
from perfbench import blocks


def read(ctx):
    return blocks.share(ctx, lambda block, op, part, stage: op == "MoEFFN")
