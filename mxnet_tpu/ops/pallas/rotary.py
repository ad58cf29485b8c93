"""Rotary position embedding as one pass over the tensor laid out by head.

The op's tensor is (B, S, heads * head_dim). Written in ``jnp`` it is
reshaped to (B, S, heads, head_dim), which makes the heads the second-minor
axis of an (8, 128) tile: XLA then writes a float32 copy of the whole tensor
each way, slices at half a head and pads the halves back, seven times the
bytes of one read and one write (ISSUE 36's table). The kernel works on
(B heads, S, head_dim), the layout the attention kernel reads and the one a
projection (or a q/k norm fused into it) writes for nothing: a row of lanes
is one head at one position, and the exchange of its two halves is a lane
rotation (``pltpu.roll``). One float32 table a head wide carries the angles,
[cos, sin, 0 ...], from which a row tile makes ``C`` = [cos, cos, 1 ...] and
``S`` = [-sin, sin, 0 ...] once for all its heads, so

    out = x * C + pair(x) * S,   pair(x)[j] = x[j + r/2] in the first half
                                 of the rotated part, x[j - r/2] in the
                                 second

in float32 with one rounding to the input's dtype. Lanes outside the rotated
part meet 1 and 0, so they pass. The transpose of the rotation is the
rotation by the negative angle: the same kernel over the same table with
``S`` negated, which is how ``ops/attention_ops.py`` differentiates the op
where it runs the kernel. Elsewhere the op is :func:`rotate_reference`,
differentiated by JAX.

Why by head and not over (B S, heads * head_dim) with a head a run of lanes:
a kernel's operand layout is fixed, and XLA meets a row-major operand with a
position-minor float32 copy between a q/k norm and the kernel and with a
transposition between the kernel and the attention. That kernel ran at 600
GB/s alone and SDAR's step lost 1.5% (my chip runs, PR 36);
``tests/test_tpu_compile.py`` prices the path for the described chip.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ... import profiler

_LANES = 128
# a grid step costs 0.125-0.144 us (PR 34) and 819 GB/s move a megabyte in
# 1.3 us: a block is up to this many rows, and heads up to these bytes
_ROWS = 512
_BLOCK_BYTES = 1 << 20


def rotate_reference(data, cos, sin, head_dim, copies=1):
    """The rotation in plain ``jnp``: ``data`` (B, S, heads * head_dim),
    ``cos`` and ``sin`` (S / copies, r / 2) float32 for the first r dims of
    every head, dimension i paired with i + r / 2; the rest pass."""
    b, s, e = data.shape
    half = cos.shape[-1]
    if copies > 1:
        cos, sin = jnp.tile(cos, (copies, 1)), jnp.tile(sin, (copies, 1))
    cos, sin = cos[None, :, None, :], sin[None, :, None, :]
    x = data.reshape(b, s, e // head_dim, head_dim).astype(jnp.float32)
    x1, x2, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)
    return out.reshape(b, s, e).astype(data.dtype)


def _block(positions, heads, head_dim, itemsize):
    """(rows, heads) of the kernel's block over a (heads, S, head_dim) tensor
    whose positions repeat every ``positions`` rows, or None where the kernel
    does not take the shape: a head that is no whole number of 128-lane
    columns (a head of 64 fills half of every row of lanes: 1.09 ms for
    LFM2's q where the jnp formulation takes 0.72; my chip runs, PR 36),
    positions that no row tile divides."""
    if head_dim % _LANES:
        return None
    sublanes = 8 * 4 // itemsize            # a tile of bf16 holds 16 rows
    tile = next((t for t in (_ROWS, 256, 128, 64, 32, 16, 8)
                 if t % sublanes == 0 and positions % t == 0), None)
    if tile is None:
        return None
    most = max(_BLOCK_BYTES // (tile * head_dim * itemsize), 1)
    return tile, next(g for g in range(min(most, heads), 0, -1)
                      if heads % g == 0)


def _lane_table(cos, sin, head_dim):
    """``cos`` and ``sin`` (positions, r / 2) packed into one float32 table
    a head wide: lane j reads cos_j, then sin_(j - r/2), then zeros. (Two
    tables, [cos, cos, 1 ...] and [-sin, sin, 0 ...], would be twice the
    bytes to write, to read and to keep.)"""
    positions, half = cos.shape
    rest = jnp.zeros((positions, head_dim - 2 * half), jnp.float32)
    return jnp.concatenate([cos, sin, rest], axis=-1)


def _rotary_kernel(x_ref, t_ref, o_ref, *, half, negative):
    """A (heads, rows, head_dim) block against the row tile's (rows,
    head_dim) block of the packed table; ``half`` = r / 2; ``negative``:
    rotate by the negative angle."""
    t = t_ref[...]
    width = t.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, t.shape, 1)
    first, rotated = lane < half, lane < 2 * half

    def pair(x):
        """x[j + r/2] in the first half of the rotated part, x[j - r/2] in
        the second; what the other lanes read is multiplied by 0."""
        up = pltpu.roll(x, width - half, 1)
        return up if 2 * half == width \
            else jnp.where(first, up, pltpu.roll(x, half, 1))

    # C = [cos, cos, 1 ...] and S = [-sin, sin, 0 ...], once a row tile
    sine = -t if negative else t
    c = jnp.where(first, t, jnp.where(rotated, pair(t), 1.0))
    s = jnp.where(first, -pair(sine), jnp.where(rotated, sine, 0.0))
    for h in range(x_ref.shape[0]):
        x = x_ref[h].astype(jnp.float32)
        o_ref[h] = (x * c + pair(x) * s).astype(o_ref.dtype)


def kernel_plan(data, positions, head_dim, impl=None):
    """(rows, heads, interpret) of the kernel's block for ``data`` (B, S,
    heads * head_dim) whose positions repeat every ``positions`` rows, or
    None where the op keeps :func:`rotate_reference`: off the chip, and for
    the shapes :func:`_block` does not take; the choice reads the backend
    and the shape alone. ``impl`` is the tests': ``"interpret"`` runs the
    kernel through the interpreter, ``"pallas"`` builds it for the chip
    whatever the backend."""
    if impl is None and jax.default_backend() == "tpu":
        impl = "pallas"
    block = impl and _block(
        positions, data.shape[0] * data.shape[-1] // head_dim, head_dim,
        data.dtype.itemsize)
    return block + (impl == "interpret",) if block else None


def rotate_pallas(data, cos, sin, head_dim, negative, plan):
    """``data`` (B, S, heads * head_dim) rotated by the angles whose ``cos``
    and ``sin`` (S / copies, r / 2, float32) are given, or with ``negative``
    by their negatives (the rotation's transpose), by the kernel over the
    tensor laid out by head, (B heads, S, head_dim); ``plan`` from
    :func:`kernel_plan`. The two transpositions are logical: XLA folds the
    first into what produces ``data`` (a projection writes its heads apart
    for nothing) and cancels the second against the attention's own. The
    table's block follows the row tile alone (tile i reads table tile i mod
    the table's tiles: every copy of a document the same rows) and the heads
    are the grid's inner axis, so a table block is fetched once a row
    tile."""
    tile, group, interpret = plan
    b, s, e = data.shape
    heads = e // head_dim
    table = _lane_table(cos, sin, head_dim)
    table_tiles = table.shape[0] // tile
    profiler.count("rotary.kernel_calls")
    by_head = pl.BlockSpec((group, tile, head_dim), lambda i, j: (j, i, 0))
    out = pl.pallas_call(
        functools.partial(_rotary_kernel, half=cos.shape[-1],
                          negative=negative),
        grid=(s // tile, b * heads // group),
        in_specs=[by_head, pl.BlockSpec(
            (tile, head_dim), lambda i, j: (i % table_tiles, 0))],
        out_specs=by_head,
        out_shape=jax.ShapeDtypeStruct((b * heads, s, head_dim), data.dtype),
        interpret=interpret,
        name="rotary_embedding",
    )(data.reshape(b, s, heads, head_dim).transpose(0, 2, 1, 3)
      .reshape(b * heads, s, head_dim), table)
    return out.reshape(b, heads, s, head_dim).transpose(0, 2, 1, 3) \
        .reshape(b, s, e)
