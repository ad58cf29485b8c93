"""The routed layer's rows between token order and slot order, as two kernels
that are each other's transpose.

``parallel/moe.py held_experts_apply`` sorts a layer's T k token-choices by
expert (slot ``j T + t`` is token t's choice j; ``take`` lists the slots in
sorted order, ``put`` is its inverse) and moves rows between the two orders
four times a step and layer: the dispatch (token rows to sorted rows), the
combine (sorted rows back to their tokens, weighted and summed over k), and
the transposes of both. On a TPU v5e, XLA's row gathers move them at about
245 GB/s of its 819 (PERF.md section 5). Here:

* :func:`rows_to_slots`, the gather: row r of the result is source row
  ``src_row[r]``, copied exactly, or scaled by a float32 factor and selected by
  a flag in float32 and rounded once (the combine's transpose, which also
  gives the dot of each result row's source row with a second operand's row r:
  the weights' cotangent). The source is at most T rows, so the kernel holds
  all of it in VMEM, each row laid out as one run of (sublanes, 128) words
  (one XLA relayout of T rows before the kernel), reads a row with one aligned
  load, and turns 16 rows at a time back into the (16, 128) tiles of the
  result with sublane-strided loads and two integer selects a pair of rows.
* :func:`slots_to_rows`, the weighted gather-sum: token t's result is the sum
  over j = 0 .. k-1, in that order, of ``select(held, src[put[j T + t]], 0) *
  w``, widened to float32 and rounded once: the combine, and with w = 1 and
  every slot held the dispatch's transpose. Its source is the k T sorted rows
  (0.5 GB on SDAR's shape), too large for VMEM, and a DMA moves whole tiles
  of 16 rows, so the rows go to slot order first, one DMA a row into an array
  where each row is one run of words, and a second kernel reads each token's
  k runs in order, turns them back into tiles, and sums.

Both walk their rows in ``lax.fori_loop`` over fixed-size groups, so the
kernel's module does not grow with T or k (an unrolled row loop would make a
large Mosaic module, slow to lower and large to store), and read their indices
a block at a time from SMEM. :func:`row_plan` decides where the kernels run: on
the chip, bfloat16 or float32 rows whose width is a whole number of 32-bit
(8, 128) tiles; elsewhere the caller keeps its ``jnp`` formulation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ... import profiler

# The kernels' loop bodies store through Pallas refs: stores the compiled
# kernel makes in every iteration, not Python state written once at trace
# time.
# tpu-lint: disable=trace-time-side-effects

_LANES = 128
# a 32-bit word's two bfloat16 halves (numpy scalars: no device work)
_LOW, _HIGH, _HALF = np.uint32(0xFFFF), np.uint32(0xFFFF0000), np.uint32(16)
# VMEM the kernels may hold: the gather's whole source and its blocks (v5e
# has 128 MiB)
_VMEM_LIMIT = 100 << 20
_SOURCE_BYTES = 64 << 20
# rows a grid step gathers, and rows (tokens) a step of the gather-sum's two
# kernels sends (sums)
_ROWS = 512
_TOKENS = 256


def row_plan(rows, width, dtype, impl=None):
    """A plan for :func:`rows_to_slots` / :func:`slots_to_rows` over a
    (``rows``, ``width``) source of ``dtype``: ``(pack, interpret)``, with
    ``pack`` the rows of the dtype a 32-bit word holds. None where the caller
    keeps its ``jnp`` formulation: off the chip, and for the shapes the
    kernels do not take: a dtype other than bfloat16 or float32, a width that
    is no whole number of (8, 128) tiles of 32-bit words (2048 bfloat16
    lanes, 1024 float32), a row count off 128 (the blocks of rows and tokens
    are whole lanes of indices), or a source that does not fit the VMEM the
    gather may hold. ``impl`` is the tests': ``"interpret"`` runs the kernels
    through the interpreter, ``"pallas"`` builds them for the chip whatever
    the backend."""
    if impl is None and jax.default_backend() == "tpu":
        impl = "pallas"
    dtype = jnp.dtype(dtype)
    if not impl or dtype not in (jnp.dtype(jnp.bfloat16),
                                 jnp.dtype(jnp.float32)):
        return None
    pack = 4 // dtype.itemsize
    if width % (_LANES * 8 * pack) or rows % _LANES \
            or rows * width * dtype.itemsize > _SOURCE_BYTES:
        return None
    return pack, impl == "interpret"


def _block(n, most, tile):
    """The largest multiple of ``tile`` up to ``most`` that divides ``n``."""
    return next(b for b in range(min(most, n) // tile * tile, 0, -tile)
                if n % b == 0)


# -- the gather ---------------------------------------------------------------

def _widen(word, half):
    """The float32 of the bfloat16 in the low (0) or high (1) half of each
    32-bit word: the half moved to the word's top, exactly."""
    return pltpu.bitcast(word << _HALF if half == 0 else word & _HIGH,
                         jnp.float32)


class _Words:
    """A bfloat16 ref read and written as (8, 128) tiles of 32-bit words
    (word p of a tile: rows 2p and 2p + 1 of its 16, the low half first):
    on the chip a view of the same memory; the interpreter, which takes no
    such view, bitcasts each tile as it passes (the same words)."""

    def __init__(self, ref, interpret):
        self.ref, self.interpret = ref, interpret
        self.view = None if interpret else ref.bitcast(jnp.uint32)

    def __getitem__(self, at):
        """``at``: (tile, lanes) or (leading index,)."""
        if self.view is not None:
            return self.view[self._index(at)]
        return pltpu.bitcast(self.ref[self._index(at, 2)], jnp.uint32)

    def __setitem__(self, at, word):
        if self.view is not None:
            self.view[self._index(at)] = word
        else:
            self.ref[self._index(at, 2)] = pltpu.bitcast(word, self.ref.dtype)

    @staticmethod
    def _index(at, rows=1):
        if len(at) == 1:                       # a whole (sublanes, 128) run
            return at[0]
        tile, lanes = at
        return pl.ds(pl.multiple_of(tile * 8 * rows, 8 * rows), 8 * rows), \
            lanes


def _gather_kernel(idx_ref, src_hbm, *refs, pack, scaled, dotted,
                   interpret):
    """One grid step: ``out_ref``'s R rows from the source rows ``idx_ref``
    names. At the first step the whole source, (T, G, 128) (row a one run of
    G 128-lane columns), is copied into ``src``; every step then walks its
    rows 16 (bfloat16) or 8 (float32) at a time: each source row is one
    aligned load of 32-bit words into ``stage``, and the result's tile of
    every column pair is two sublane-strided loads from ``stage`` and two
    integer selects (bfloat16 rows share words: word m of a row holds its
    columns 2m and 2m + 1, word p of a tile its rows 2p and 2p + 1)."""
    refs = list(refs)
    aux_ref = refs.pop(0) if scaled else None
    other_ref = refs.pop(0) if dotted else None
    out_ref = refs.pop(0)
    dw_ref = refs.pop(0) if dotted else None
    src, stage, sem = refs[:3]
    cols = refs[3] if scaled else None
    part = refs[-1] if dotted else None
    rows, width = out_ref.shape
    groups = width // _LANES                   # 128-lane columns of a row
    words = groups // pack                     # 32-bit words' rows of a row
    unit = 8 * pack                            # rows of a tile of the result
    if pack == 2:
        src32, out32 = _Words(src, interpret), _Words(out_ref, interpret)
    else:
        src32, out32 = src, out_ref

    @pl.when(pl.program_id(0) == 0)
    def _():
        copy = pltpu.make_async_copy(src_hbm, src, sem)
        copy.start()
        copy.wait()

    if scaled:
        # the block's factors and flags as columns, once a block
        cols[...] = aux_ref[0].T               # (R, 8): scale, held, 0 ...

    def tile(q, carry):
        base = pl.multiple_of(q * unit, unit)
        rows_of = pl.ds(base, unit)
        for u in range(unit):
            stage[pl.ds(u * words, words), :] = \
                src32[idx_ref[0, 0, base + u],]
        if scaled:
            factor = cols[rows_of, :]
            held, factor = factor[:, 1:2] > 0, factor[:, 0:1]
        if dotted:
            acc = jnp.zeros((unit, _LANES), jnp.float32)
        for m in range(words):
            if pack == 2:
                even = stage[pl.ds(m, 8, stride=2 * words), :]
                odd = stage[pl.ds(words + m, 8, stride=2 * words), :]
                got = ((2 * m, (even & _LOW) | (odd << _HALF)),
                       (2 * m + 1, (even >> _HALF) | (odd & _HIGH)))
            else:
                got = ((m, stage[pl.ds(m, 8, stride=words), :]),)
            for g, word in got:
                lanes = pl.ds(g * _LANES, _LANES)
                if not scaled:
                    out32[(q, lanes) if pack == 2 else (rows_of, lanes)] = \
                        word
                    continue
                wide = (pltpu.bitcast(word, out_ref.dtype) if pack == 2
                        else word).astype(jnp.float32)
                if dotted:
                    mine = other_ref[rows_of, lanes].astype(jnp.float32)
                    acc = acc + jnp.where(held, mine, 0) * wide
                out_ref[rows_of, lanes] = jnp.where(
                    held, wide * factor, 0).astype(out_ref.dtype)
        if dotted:
            part[rows_of, :] = acc
        return carry

    jax.lax.fori_loop(0, rows // unit, tile, 0)
    if dotted:
        for c in range(rows // _LANES):
            chunk = part[pl.ds(c * _LANES, _LANES), :]
            dw_ref[0, :, pl.ds(c * _LANES, _LANES)] = jnp.sum(
                chunk.T, axis=0, keepdims=True)


# jitted, so that a process traces each kernel once a shape: a step's routed
# layers share their shapes, and shape inference at bind traces them again
# in every process, before the stored step is loaded
@functools.partial(jax.jit, static_argnames=("plan",))
def rows_to_slots(src, src_row, plan, scale=None, held=None, other=None):
    """``src[src_row]``: ``src`` (T, d), ``src_row`` (n,) int32 in [0, T),
    ``plan`` from :func:`row_plan`. With ``scale`` (n,) float32 and ``held``
    (n,) bool the result's row r is ``select(held[r], scale[r] * src[src_row
    [r]], 0)`` in float32, rounded once; with ``other`` (n, d) too, the
    function also returns (n,) float32, ``select(held[r], other[r], 0) .
    src[src_row[r]]`` in float32 (the row the result came from, unscaled)."""
    pack, interpret = plan
    t, d = src.shape
    n = src_row.shape[0]
    rows = _block(n, _ROWS, _LANES)
    grid = n // rows
    scaled, dotted = scale is not None, other is not None
    profiler.count("moe.row_kernel_calls")
    by_block = pl.BlockSpec((rows, d), lambda i: (i, 0))
    in_specs = [pl.BlockSpec((1, 1, rows), lambda i: (i, 0, 0),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pl.ANY)]
    args = [src_row.astype(jnp.int32).reshape(grid, 1, rows),
            src.reshape(t, d // _LANES, _LANES)]
    if scaled:
        aux = jnp.zeros((grid, 8, rows), jnp.float32)
        aux = aux.at[:, 0].set(scale.astype(jnp.float32).reshape(grid, rows))
        aux = aux.at[:, 1].set(held.astype(jnp.float32).reshape(grid, rows))
        in_specs.append(pl.BlockSpec((1, 8, rows), lambda i: (i, 0, 0)))
        args.append(aux)
        scratch_cols = [pltpu.VMEM((rows, 8), jnp.float32)]
    else:
        scratch_cols = []
    out_specs = [by_block]
    out_shape = [jax.ShapeDtypeStruct((n, d), src.dtype)]
    scratch = [pltpu.VMEM((t, d // _LANES, _LANES), src.dtype),
               pltpu.VMEM((8 * pack * d // _LANES // pack, _LANES),
                          jnp.uint32 if pack == 2 else src.dtype),
               pltpu.SemaphoreType.DMA(())] + scratch_cols
    if dotted:
        in_specs.append(by_block)
        args.append(other)
        out_specs.append(pl.BlockSpec((1, 1, rows), lambda i: (i, 0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((grid, 1, rows), jnp.float32))
        scratch.append(pltpu.VMEM((rows, _LANES), jnp.float32))
    out = pl.pallas_call(
        functools.partial(_gather_kernel, pack=pack, scaled=scaled,
                          dotted=dotted, interpret=interpret),
        grid=(grid,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="moe_rows_to_slots",
    )(*args)
    return (out[0], out[1].reshape(n)) if dotted else out[0]


# -- the weighted gather-sum --------------------------------------------------

def _scatter_kernel(slot_ref, src_ref, out_hbm, stage, sem, *, pack):
    """One grid step: R sorted rows, read as whole tiles, laid out in
    ``stage`` one run of 32-bit words a row (sublane-strided stores: the
    gather's layout), and each run sent to its slot's place in ``out_hbm``
    by one DMA of (words, 128). The step waits for its copies before it
    ends, so ``stage`` is free for the next."""
    rows, width = src_ref.shape
    groups = width // _LANES
    words = groups // pack
    unit = 8 * pack

    def tile(q, carry):
        base = pl.multiple_of(q * unit, unit)
        at = base * words
        for m in range(words):
            if pack == 2:
                lo, hi = (pltpu.bitcast(src_ref[pl.ds(base, unit), pl.ds(
                    g * _LANES, _LANES)], jnp.uint32) for g in (2 * m,
                                                               2 * m + 1))
                stage[pl.ds(at + m, 8, stride=2 * words), :] = \
                    (lo & _LOW) | (hi << _HALF)
                stage[pl.ds(at + words + m, 8, stride=2 * words), :] = \
                    (lo >> _HALF) | (hi & _HIGH)
            else:
                stage[pl.ds(at + m, 8, stride=words), :] = \
                    src_ref[pl.ds(base, unit), pl.ds(m * _LANES, _LANES)]
        for u in range(unit):
            pltpu.make_async_copy(
                stage.at[pl.ds(pl.multiple_of(at + u * words, words), words)],
                out_hbm.at[slot_ref[0, 0, base + u]], sem).start()
        return carry

    jax.lax.fori_loop(0, rows // unit, tile, 0)

    def wait(r, carry):
        pltpu.make_async_copy(stage.at[pl.ds(0, words)], out_hbm.at[0],
                              sem).wait()
        return carry

    jax.lax.fori_loop(0, rows, wait, 0)


def _to_slot_order(src, slot, plan):
    """``src`` (n, d) rows, ``slot`` (n,) int32 a permutation: returns (n,
    words, 128) 32-bit words (the dtype's own where it is 32-bit; words = d /
    128 / pack), run ``slot[r]`` holding row r."""
    pack, interpret = plan
    n, d = src.shape
    words = d // _LANES // pack
    rows = _block(n, _TOKENS, _LANES)
    grid = n // rows
    wdt = jnp.uint32 if pack == 2 else src.dtype
    return pl.pallas_call(
        functools.partial(_scatter_kernel, pack=pack),
        grid=(grid,),
        in_specs=[pl.BlockSpec((1, 1, rows), lambda i: (i, 0, 0),
                               memory_space=pltpu.SMEM),
                  pl.BlockSpec((rows, d), lambda i: (i, 0))],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct((n, words, _LANES), wdt),
        scratch_shapes=[pltpu.VMEM((rows * words, _LANES), wdt),
                        pltpu.SemaphoreType.DMA(())],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="moe_rows_to_slot_order",
    )(slot.astype(jnp.int32).reshape(grid, 1, rows), src)


def _sum_kernel(*refs, pack, weighted, dtype):
    """One grid step: R tokens' k runs of words, choice by choice in order;
    each 16 (bfloat16) or 8 (float32) tokens' runs are turned back into
    tiles (sublane-strided loads), widened, selected and weighted (each
    choice's weights and flags made columns once) and added to the tokens'
    float32 sum in ``acc``, which is then rounded once and written."""
    refs = list(refs)
    aux_ref = refs.pop(0) if weighted else None
    src_ref, out_ref, acc = refs[:3]
    cols = refs[3] if weighted else None
    k = src_ref.shape[0]
    tokens, width = out_ref.shape
    groups = width // _LANES
    words = groups // pack
    unit = 8 * pack
    if weighted:
        cols[...] = aux_ref[0].T           # (R, 2 k8): weights, flags
        lane = jax.lax.broadcasted_iota(jnp.int32, (unit, cols.shape[1]), 1)

    def tile(q, carry):
        base = pl.multiple_of(q * unit, unit)
        at = base * words
        acc[...] = jnp.zeros(acc.shape, jnp.float32)

        def choice(j, carry):
            if weighted:
                mine = cols[pl.ds(base, unit), :]
                w = jnp.sum(jnp.where(lane == j, mine, 0), 1, keepdims=True)
                held = jnp.sum(jnp.where(lane == j + cols.shape[1] // 2,
                                         mine, 0), 1, keepdims=True) > 0
            for m in range(words):
                if pack == 2:
                    even = src_ref[j, pl.ds(at + m, 8, stride=2 * words), :]
                    odd = src_ref[j, pl.ds(at + words + m, 8,
                                           stride=2 * words), :]
                    got = [pltpu.bitcast((even & _LOW) | (odd << _HALF),
                                         dtype),
                           pltpu.bitcast((even >> _HALF) | (odd & _HIGH),
                                         dtype)]
                else:
                    got = [src_ref[j, pl.ds(at + m, 8, stride=words), :]]
                for h, g in enumerate(got):
                    g = g.astype(jnp.float32)
                    if weighted:
                        g = jnp.where(held, g, 0) * w
                    lanes = pl.ds((pack * m + h) * _LANES, _LANES)
                    acc[:, lanes] = acc[:, lanes] + g
            return carry

        jax.lax.fori_loop(0, k, choice, 0)
        out_ref[pl.ds(base, unit), :] = acc[...].astype(dtype)
        return carry

    jax.lax.fori_loop(0, tokens // unit, tile, 0)


@functools.partial(jax.jit, static_argnames=("k", "plan"))
def slots_to_rows(src, take, k, plan, weights=None, held=None):
    """``src`` (k T, d) rows in sorted order, ``take`` (k T,) int32 the slot
    (``j T + t``: token t's choice j) of each. Returns (T, d) in ``src``'s
    dtype, token t the float32 sum over j = 0 .. k-1, in that order, of
    ``select(held[j, t], src[row of slot j T + t], 0) * weights[j, t]``
    rounded once; without ``weights`` and ``held`` the plain sum of the k
    rows. A slot that is not held is selected away before it is multiplied.

    Two kernels: the sorted rows, read in whole tiles, each sent by one DMA
    to its slot's place in a (k T, words, 128) array of 32-bit words where a
    row is one run; then tokens in blocks, their k runs read in order and
    turned back into tiles, weighted and summed. (A row of the sorted
    operand cannot be read alone where it lies: a DMA moves whole tiles of
    16 rows; on a TPU v5e a one-row load from VMEM cost 17-19 cycles a row
    and slab of lanes, and an update of a float32 sum of all tokens held in
    VMEM, row by row as the sorted rows are read, 60-90 ns a row: both
    slower than XLA.)"""
    pack, interpret = plan
    n, d = src.shape
    t = n // k
    words = d // _LANES // pack
    profiler.count("moe.row_kernel_calls")
    by_slot = _to_slot_order(src, take, plan).reshape(k, t * words, _LANES)
    tokens = _block(t, _TOKENS, _LANES)
    weighted = weights is not None
    in_specs, args = [], []
    scratch = [pltpu.VMEM((8 * pack, d), jnp.float32)]
    if weighted:
        # weights in rows 0 .. k-1, flags in rows k8 .. k8 + k-1
        k8 = -(-k // 8) * 8
        aux = jnp.zeros((2 * k8, t), jnp.float32)
        aux = aux.at[:k].set(weights.astype(jnp.float32))
        aux = aux.at[k8:k8 + k].set(held.astype(jnp.float32))
        aux = aux.reshape(2 * k8, t // tokens, tokens).transpose(1, 0, 2)
        in_specs.append(pl.BlockSpec((1, 2 * k8, tokens), lambda i: (i, 0, 0)))
        args.append(aux)
        scratch.append(pltpu.VMEM((tokens, 2 * k8), jnp.float32))
    in_specs.append(pl.BlockSpec((k, tokens * words, _LANES),
                                 lambda i: (0, i, 0)))
    args.append(by_slot)
    return pl.pallas_call(
        functools.partial(_sum_kernel, pack=pack, weighted=weighted,
                          dtype=src.dtype),
        grid=(t // tokens,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((tokens, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((t, d), src.dtype),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="moe_slots_to_rows",
    )(*args)
