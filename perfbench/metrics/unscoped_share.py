"""Device time of the step's operations that carry no scope of the program
(no ``op_name``, or one with nothing before its primitive: copies the
compiler put in, the partitioner's collectives, the step's own glue) over
the step programs' device time on the busiest chip, in percent."""
from perfbench import scopes


def read(ctx):
    return scopes.share_of_step(ctx, lambda scope, back: scope is None)
