"""Device time of the operations scoped ``GroupedQueryAttention/`` (the
first forward, the forward recomputed in the backward, and the backward:
head layout, the attention itself, the output gate) over the step programs'
device time on the busiest chip, in percent."""
from perfbench import blocks


def read(ctx):
    return blocks.share(
        ctx, lambda block, op, part, stage: op == "GroupedQueryAttention")
