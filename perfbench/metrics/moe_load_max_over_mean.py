"""How uneven the routing left the held experts: the fullest held expert's
token-choices over the mean held expert's, from the program's own counters
(``moe.load_max`` and ``moe.assignments_held``, each summed over the routed
layers and the window's steps on the device, read at the window's two ends
by ``Program.counters()`` into the program's counters of those names). 1 is
even routing; the grouped matmul's longest group is this many times the
mean. Nothing to read from a program without the counters."""
from perfbench import blocks


def read(ctx):
    routed = blocks.routed_window(ctx)
    if not routed:
        return None
    return routed["moe.load_max"] / (
        routed["moe.assignments_held"] / ctx["cfg"]["num_experts_held"])
