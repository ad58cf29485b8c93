"""Seconds of set-up spent binding: the summed duration of the ``bind``
spans that end before the window (``SPMDTrainer.bind``; under ``Module``
the stages ``bind``, ``init_params``, ``init_optimizer`` and the step
program's ``fused_step``: the plan and the graph passes, the parameters
taken to the device, the optimizer state made there), less what the compile
work under them covers, which ``setup_compile_s`` counts.

Entry as it will stand: unit ``s``, ``better: lower``, ``source:
program_span``, ``layer: step runtime and compile``, ``moves: setup_s``, no
``workloads`` list. None where the program records no span of set-up."""
from perfbench import readers


def read(ctx):
    got = readers.setup_spans(ctx)
    if got is None:
        return None
    return readers.seconds_less_compiles(readers.ended(got[0], got[2]),
                                         "bind")
