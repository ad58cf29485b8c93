"""Device time of the operations scoped ``BatchNorm/``, ``Activation/``,
``Pooling/`` and ``elemwise_add/`` (forward and transposed) over the step
programs' device time on the busiest chip, in percent: the memory-bound
part of a convolutional step. Fusions are charged as in
``scopes.scoped_seconds``."""
from perfbench import scopes

TYPES = {"BatchNorm", "Activation", "Pooling", "elemwise_add"}


def read(ctx):
    return scopes.share_of_step(
        ctx, lambda scope, back: scopes.op_type(scope) in TYPES) or None
