"""Seconds of set-up spent making programs: the summed duration of the
``compile.materialize`` spans (a stored executable read and loaded, or the
step body traced, lowered, compiled, its op map parsed and the executable
stored: ``mxnet_tpu/compiler/aot.py``) plus what JAX reports of a trace, a
lowering or a backend compile under none of them (``jax.trace``,
``jax.lower``, ``jax.backend_compile``: the programs that pass through no
``PersistentJit``), of those that end before the window.

Entry as it will stand: unit ``s``, ``better: lower``, ``source:
program_span``, ``layer: step runtime and compile``, ``moves: setup_s``, no
``workloads`` list (read wherever ``setup_s`` is). None where the program
records no span of set-up."""
from perfbench import readers


def read(ctx):
    got = readers.setup_spans(ctx)
    if got is None:
        return None
    found = readers.ended(got[0], got[2])
    return sum(s.end_ns - s.start_ns
               for s in readers.compile_tops(found)) / readers.NS
