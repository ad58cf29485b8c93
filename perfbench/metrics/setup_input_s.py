"""Seconds of set-up spent constructing the iterators: the summed duration
of the ``input.construct`` spans that end before the window
(``NDArrayIter.__init__``: the sources sent to the device and read back as
the host cache; ``PrefetchingIter.__init__``), less what the compile work
under them covers, which ``setup_compile_s`` counts. 0 where the feed is
resident and the program constructs no iterator.

Entry as it will stand: unit ``s``, ``better: lower``, ``source:
program_span``, ``layer: input pipeline``, ``moves: setup_s``, no
``workloads`` list. None where the program records no span of set-up."""
from perfbench import readers


def read(ctx):
    got = readers.setup_spans(ctx)
    if got is None:
        return None
    return readers.seconds_less_compiles(readers.ended(got[0], got[2]),
                                         "input.construct")
