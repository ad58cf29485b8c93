"""Device time of the operations scoped ``ShortConv/`` (the input
projection, the two gates and the taps between them, the output projection;
first forward, forward recomputed in the backward, and backward) over the
step programs' device time on the busiest chip, in percent. Nothing to read
in a program whose graph has no such op."""
from perfbench import blocks


def read(ctx):
    return blocks.share(ctx,
                        lambda block, op, part, stage: op == "ShortConv")
