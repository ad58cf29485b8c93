"""Share of set-up that lies in no span of the program, in percent: of the
interval from the start of the process (the harness's own stamp,
``perfbench.run.T_PROCESS``, on the spans' clock) to the start of the
window, the part that the union of every span of every thread, cut to the
interval, leaves. It is the harness's own work (weights and batches from the
seed, the read-backs of the checked steps outside the fit loop's spans) and
what happens before ``import mxnet_tpu``: ``jax``'s import and the device's
start.

Entry as it will stand: unit ``%``, ``better: lower``, ``source:
program_span``, ``layer: whole set-up``, ``moves: setup_s``, no
``workloads`` list. None where the program records no span of set-up."""
from perfbench import readers


def read(ctx):
    got = readers.setup_spans(ctx)
    if got is None:
        return None
    found, lo, hi = got
    if hi <= lo:
        return None
    covered = readers.covered_seconds(found, lo, hi)
    return 100.0 * (1.0 - covered * readers.NS / (hi - lo))
