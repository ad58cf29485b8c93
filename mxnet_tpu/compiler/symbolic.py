"""Symbolic-dim programs: one compiled executable per dim RANGE.

Relay's shape-polymorphic typed IR (arxiv 1810.00952) compiles one
program for a dim range instead of one per concrete extent; jax exposes
the same capability through ``jax.export`` symbolic shapes. This module
is the serving-facing seam: :class:`SymbolicBatchProgram` exports a
function ONCE with a symbolic leading (batch) dim and then serves every
row count ``1..max_rows`` from that single artifact — collapsing the
``coalescer_sizes x buckets`` warm-up matrix to one probe and the
persistent-cache footprint to one entry.

Identity discipline: the symbolic signature rides ``transform_sig`` in
:func:`~mxnet_tpu.compiler.fingerprint.program_key`
(:func:`symbolic_transform_sig`, same grammar as
``GraphIR.symbolic_signature``), so a symbolic program and a concrete
program over the same graph can never collide on one persisted key —
a stale-layout serve is structurally impossible, not just unlikely.

A function that cannot be exported with a symbolic leading dim (it
reshapes to a concrete batch, say) fails at construction with jax's own
error: there is no per-shape fallback to hide it.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import export

from .fingerprint import batch_signature, graph_fingerprint, program_key

__all__ = ["symbolic_transform_sig", "SymbolicBatchProgram"]


def symbolic_transform_sig(names: Sequence[str], max_rows: int,
                           axis: int = 0) -> str:
    """The ``transform_sig`` fragment a symbolic-batch program carries
    into :func:`program_key` — same grammar as
    ``GraphIR.symbolic_signature`` so graph-level and serving-level
    declarations read identically."""
    return "symdims=" + ",".join(
        f"{name}@{int(axis)}<={int(max_rows)}" for name in sorted(names))


class SymbolicBatchProgram:
    """One exported program serving every batch size up to ``max_rows``.

    ``fn`` takes a ``{name: array}`` dict and returns a list of arrays
    (the serving backend calling convention). ``input_specs`` maps each
    input name to its PER-ROW shape (without the batch axis);
    ``input_dtypes`` defaults every input to float32.

    ``compiles`` is 1 for the life of the program; :attr:`key` is the
    persisted program identity, with the symbolic signature riding
    ``transform_sig``.
    """

    def __init__(self, fn: Callable[[Dict], List], input_specs: Dict,
                 max_rows: int, input_dtypes: Optional[Dict] = None,
                 name: str = "symbolic_batch"):
        self.fn = fn
        self.name = name
        self.max_rows = max(1, int(max_rows))
        self.input_specs = {k: tuple(v) for k, v in input_specs.items()}
        self.input_dtypes = {
            k: np.dtype((input_dtypes or {}).get(k, np.float32))
            for k in self.input_specs}
        self._exported = self._export()
        self.compiles = 1
        self.transform_sig = symbolic_transform_sig(
            sorted(self.input_specs), self.max_rows)
        self.key = self._program_key()

    # ``fn`` sees dict-in/list-out; jax traces it positionally by name so
    # the export calling convention is stable under dict ordering.
    def _call_fn(self, arrays: Dict):
        outs = self.fn(dict(arrays))
        return list(outs) if isinstance(outs, (list, tuple)) else [outs]

    def _export(self):
        scope = export.SymbolicScope([f"_b <= {self.max_rows}"])
        structs = {}
        for iname, row in sorted(self.input_specs.items()):
            rest = ", ".join(str(d) for d in row)
            spec = f"_b, {rest}" if rest else "_b"
            shape = export.symbolic_shape(spec, scope=scope)
            structs[iname] = jax.ShapeDtypeStruct(
                shape, jnp.dtype(self.input_dtypes[iname]))
        exported = export.export(jax.jit(self._call_fn))(structs)
        # prove the range before promising it: the two extents that
        # break most often (degenerate 1 and the bound itself)
        for rows in {1, self.max_rows}:
            exported.call(self._zeros(rows))
        return exported

    def _zeros(self, rows: int) -> Dict[str, np.ndarray]:
        return {iname: np.zeros((rows,) + row, self.input_dtypes[iname])
                for iname, row in self.input_specs.items()}

    def _program_key(self) -> str:
        try:
            fp = graph_fingerprint(self.fn)
        except Exception:
            fp = f"callable:{self.name}"
        avals = batch_signature(
            self._zeros(self.max_rows), route=self.name,
            symbolic_rows=self.max_rows)
        return program_key("symbolic_batch", fp, avals,
                           transform_sig=self.transform_sig)

    def __call__(self, arrays: Dict) -> List[np.ndarray]:
        outs = self._exported.call(dict(arrays))
        return [np.asarray(o) for o in outs]
