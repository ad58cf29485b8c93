"""Fused LSTM: the cell as one Pallas kernel, and the recurrence of one layer
and direction, forward and backward.

A plain jnp cell emits a matmul plus ~10 pointwise ops per step that XLA
fuses only partially across the scan boundary; the kernels do the
h-projection on the MXU and all four gate nonlinearities + state update in
a single VPU pass over VMEM-resident blocks. Gate order i,f,g,o matches the
RNN op's cuDNN packing (rnn_ops.py).

``lstm_recurrence`` is what the RNN op runs. Forward: where ``W_hh`` and one
step's blocks fit in VMEM together, ONE kernel (``lstm_cell_scan``) walks the
time steps on its grid with the weight and the carried ``(h, c)`` held in
VMEM and each step's projection streamed in; where they do not (and off the
chip), ``lax.scan`` runs the per-step cell. Nothing of the gates is kept:
the backward gets ``(xproj, h0, c0, h and c of every step, W_hh)``.

Autodiff can't see through pallas_call, so the backward is written by hand,
as a whole: the pre-activations ``xproj + Hprev @ W_hh^T`` of a run of time
steps are recomputed in one matmul; the steps are walked backwards carrying
``(dh, dc)`` with the pointwise adjoints in float32 (``_gate_adjoints``) and
ONE matmul a step, ``dgates_t @ W_hh`` (operands in the weight's dtype as the
forward's, float32 accumulation) -- on the chip by one kernel
(``lstm_bwd_step``) built like the forward's, elsewhere by ``lax.scan``;
``dgates`` is stacked in the weight's dtype and ``dW_hh`` is one matmul over
the stack after the walk.

``lstm_cell_fused`` (one step, for direct callers) keeps a per-step VJP: the
same adjoints, then the step's own ``dh`` and ``dW_hh`` matmuls.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ... import profiler


def _activations(g):
    H = g.shape[-1] // 4
    return (jax.nn.sigmoid(g[:, 0 * H:1 * H]),
            jax.nn.sigmoid(g[:, 1 * H:2 * H]),
            jnp.tanh(g[:, 2 * H:3 * H]),
            jax.nn.sigmoid(g[:, 3 * H:4 * H]))


def _preactivations(xproj, h, w_h2h):
    return xproj.astype(jnp.float32) + jax.lax.dot_general(
        h.astype(jnp.float32), w_h2h.astype(jnp.float32),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)


def _gates(xproj, h, w_h2h):
    return _activations(_preactivations(xproj, h, w_h2h))


def _cell_jnp(xproj, h, c, w_h2h):
    i, f, g, o = _gates(xproj, h, w_h2h)
    c_new = f * c.astype(jnp.float32) + i * g
    h_new = o * jnp.tanh(c_new)
    return h_new.astype(h.dtype), c_new.astype(c.dtype)


def _lstm_kernel(xi_ref, xf_ref, xg_ref, xo_ref, h_ref, c_ref,
                 wi_ref, wf_ref, wg_ref, wo_ref, hn_ref, cn_ref):
    """One block of ``bh`` hidden units: each gate's x-projection block
    (N, bh) and h2h weight block (bh, H), the whole h (N, H)."""
    h = h_ref[:]

    def gate(x_ref, w_ref):
        w = w_ref[:]
        lhs = h
        if lhs.dtype != w.dtype:
            lhs, w = lhs.astype(jnp.float32), w.astype(jnp.float32)
        # bf16 operands are exact on the MXU with f32 accumulation; a
        # global "highest" default precision must not reach them (Mosaic
        # refuses an fp32-precision matmul of bf16 operands)
        precision = (jax.lax.Precision.DEFAULT
                     if w.dtype == jnp.bfloat16 else None)
        return x_ref[:].astype(jnp.float32) + jax.lax.dot_general(
            lhs, w, (((1,), (1,)), ((), ())), precision=precision,
            preferred_element_type=jnp.float32)

    i = jax.nn.sigmoid(gate(xi_ref, wi_ref))
    f = jax.nn.sigmoid(gate(xf_ref, wf_ref))
    g = jnp.tanh(gate(xg_ref, wg_ref))
    o = jax.nn.sigmoid(gate(xo_ref, wo_ref))
    c_new = f * c_ref[:].astype(jnp.float32) + i * g
    h_new = o * jnp.tanh(c_new)
    hn_ref[:] = h_new.astype(hn_ref.dtype)
    cn_ref[:] = c_new.astype(cn_ref.dtype)


def _lstm_kernel_whole(xproj_ref, h_ref, c_ref, w_ref, hn_ref, cn_ref):
    """Every operand whole in VMEM, the gates sliced out of one (N, 4H)
    projection: for an H whose gate blocks are off the 128-lane tiling."""
    hn_ref[:], cn_ref[:] = _cell_jnp(xproj_ref[:], h_ref[:], c_ref[:],
                                     w_ref[:])


# double-buffered h2h weight blocks (4 gates x 2 buffers, sized as f32 so
# a mixed-dtype upcast still fits) stay under this share of the chip's
# 16 MiB scoped VMEM
_W_BLOCK_BUDGET = 8 << 20


def _hidden_block(hdim):
    """Hidden units per grid step: the largest multiple of 128 that
    divides H within the VMEM budget; None when H is not a multiple of
    128 (a per-gate block of xproj would be off the TPU's lane tiling)."""
    if hdim % 128:
        return None
    bh = 128
    while hdim % (2 * bh) == 0 and 8 * (2 * bh) * hdim * 4 <= _W_BLOCK_BUDGET:
        bh *= 2
    return bh


def _cell_pallas(xproj, h, c, w_h2h, interpret):
    n, hdim = h.shape
    out_shape = (jax.ShapeDtypeStruct((n, hdim), h.dtype),
                 jax.ShapeDtypeStruct((n, hdim), c.dtype))
    bh = _hidden_block(hdim)
    if bh is None:
        # whole-array blocks are always on the tiling; the weight is not
        # pipelined, so a large unaligned H runs out of VMEM and the
        # compiler says so
        return pl.pallas_call(
            _lstm_kernel_whole, out_shape=out_shape, interpret=interpret,
            name="lstm_cell",
        )(xproj, h, c, w_h2h)
    nb = hdim // bh

    # gate k's block j: columns of xproj / rows of w_h2h at k * nb + j
    x_specs = [pl.BlockSpec((n, bh), lambda j, k=k: (0, k * nb + j))
               for k in range(4)]
    w_specs = [pl.BlockSpec((bh, hdim), lambda j, k=k: (k * nb + j, 0))
               for k in range(4)]
    state_spec = pl.BlockSpec((n, bh), lambda j: (0, j))
    return pl.pallas_call(
        _lstm_kernel,
        grid=(nb,),
        in_specs=x_specs + [pl.BlockSpec((n, hdim), lambda j: (0, 0)),
                            state_spec] + w_specs,
        out_specs=(state_spec, state_spec),
        out_shape=out_shape,
        interpret=interpret,
        name="lstm_cell",
    )(xproj, xproj, xproj, xproj, h, c, w_h2h, w_h2h, w_h2h, w_h2h)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _cell(xproj, h, c, w_h2h, impl):
    if impl == "jnp":
        return _cell_jnp(xproj, h, c, w_h2h)
    return _cell_pallas(xproj, h, c, w_h2h, interpret=(impl == "interpret"))


def _cell_fwd(xproj, h, c, w_h2h, impl):
    out = _cell(xproj, h, c, w_h2h, impl)
    return out, (xproj, h, c, w_h2h)


def _gate_adjoints(preact, c_prev, dh_new, dc_new):
    """The pointwise half of one step's backward, in float32: from the
    pre-activations (N, 4H), the cell state the step started from and the
    cotangents of ``(h', c')``, the pre-activations' cotangent (N, 4H) and
    the cotangent that flows on into ``c_prev``. The gates are recomputed
    (cheap pointwise); nothing of the forward but ``c_prev`` is read."""
    i, f, g, o = _activations(preact)
    cf = c_prev.astype(jnp.float32)
    c_new = f * cf + i * g
    tc = jnp.tanh(c_new)
    dh32 = dh_new.astype(jnp.float32)
    dc = dc_new.astype(jnp.float32) + dh32 * o * (1 - tc * tc)
    d_i = dc * g * i * (1 - i)
    d_f = dc * cf * f * (1 - f)
    d_g = dc * i * (1 - g * g)
    d_o = dh32 * tc * o * (1 - o)
    return jnp.concatenate([d_i, d_f, d_g, d_o], axis=-1), dc * f


def _cell_bwd(impl, res, cts):
    xproj, h, c, w_h2h = res
    dgates, dc_prev = _gate_adjoints(_preactivations(xproj, h, w_h2h), c,
                                     *cts)
    dxproj = dgates.astype(xproj.dtype)
    dh = (dgates @ w_h2h.astype(jnp.float32)).astype(h.dtype)
    dw = jax.lax.dot_general(dgates, h.astype(jnp.float32),
                             (((0,), (0,)), ((), ()))).astype(w_h2h.dtype)
    return dxproj, dh, dc_prev.astype(c.dtype), dw


_cell.defvjp(_cell_fwd, _cell_bwd)


def _resolved(impl):
    if impl is None:
        return "pallas" if jax.default_backend() == "tpu" else "jnp"
    return impl


def lstm_cell_fused(xproj, h, c, w_h2h, impl=None):
    """One LSTM step: (xproj (N,4H), h (N,H), c (N,H), w_h2h (4H,H)) ->
    (h', c'). impl: None = the compiled Pallas kernel on a TPU backend,
    the jnp cell elsewhere; 'pallas' | 'interpret' | 'jnp' to force. On
    TPU the kernel either compiles or raises — it never gives way to the
    jnp cell on its own."""
    return _cell(xproj, h, c, w_h2h, _resolved(impl))


# -- one layer and direction: the scan over the cell, and its backward ---------

# what a kernel may take of a v5e core's 128 MiB of VMEM
_VMEM_LIMIT = 100 << 20


def _padded_bytes(shape, dtype):
    """Bytes of an array in VMEM: the last dimension on the 128-lane tiling."""
    *rows, lanes = shape
    size = -(-lanes // 128) * 128 * jnp.dtype(dtype).itemsize
    for r in rows:
        size *= r
    return size


def _start_carry(carried, initial):
    """On the grid's first step the carried blocks take their initial
    values."""
    @pl.when(pl.program_id(0) == 0)
    def _():
        for ref, first in zip(carried, initial):
            ref[:] = first[:]


def _lstm_scan_kernel(keep_c, xproj_ref, h0_ref, c0_ref, w_ref, hs_ref,
                      *rest):
    """Grid step = time step, in the scan's order: the cell of
    ``_lstm_kernel_whole`` with ``W_hh`` and the carried ``(h, c)`` (the two
    whole-array outputs) staying in VMEM over the grid, a step's input
    projection streaming in and its ``h`` (and ``c``, where the backward
    will want it) streaming out beside the arithmetic."""
    h_ref, c_ref = rest[-2:]
    _start_carry((h_ref, c_ref), (h0_ref, c0_ref))
    h, c = _cell_jnp(xproj_ref[0], h_ref[:], c_ref[:], w_ref[:])
    hs_ref[0] = h_ref[:] = h
    c_ref[:] = c
    if keep_c:
        rest[0][0] = c


def _walk_vmem(rows, hdim, w_dtype, streamed):
    """Bytes of VMEM a kernel that walks the time steps wants: W_hh once,
    a step's ``streamed`` blocks [(width, dtype)] twice (one in flight
    beside the one in use), the carried states and their initial values,
    and the float32 gate temporaries."""
    state = _padded_bytes((rows, hdim), jnp.float32)
    return _padded_bytes((4 * hdim, hdim), w_dtype) \
        + 2 * sum(_padded_bytes((rows, w), d) for w, d in streamed) \
        + 4 * state + 2 * _padded_bytes((rows, 4 * hdim), jnp.float32)


def _scan_kernel_vmem(xproj, h0, c0, w_h2h):
    n, hdim = h0.shape
    return _walk_vmem(n, hdim, w_h2h.dtype, [
        (4 * hdim, xproj.dtype), (hdim, h0.dtype), (hdim, c0.dtype)])


def _time_grid(steps, n, last_first):
    """Block specs of a kernel whose grid walks the time steps of
    (T, N, width) stacks, ``last_first`` from the last to the first, with
    whole (rows, width) arrays held in VMEM beside them."""
    walk = (lambda t: (steps - 1 - t, 0, 0)) if last_first \
        else (lambda t: (t, 0, 0))

    def streamed(width):
        return pl.BlockSpec((1, n, width), walk)

    def resident(rows, width):
        # fetched once: one buffer is enough
        return pl.BlockSpec((rows, width), lambda t: (0, 0),
                            pipeline_mode=pl.Buffered(1))

    def carried(width):
        return pl.BlockSpec((n, width), lambda t: (0, 0))

    return streamed, resident, carried


def _scan_cells_pallas(xproj, h0, c0, w_h2h, reverse, keep_c, interpret):
    steps, n, gates = xproj.shape
    hdim = gates // 4
    streamed, resident, carried = _time_grid(steps, n, reverse)
    stack = jax.ShapeDtypeStruct((steps, n, hdim), h0.dtype)
    stacks = ((stack, jax.ShapeDtypeStruct(stack.shape, c0.dtype)) if keep_c
              else (stack,))
    *kept, hT, cT = pl.pallas_call(
        functools.partial(_lstm_scan_kernel, keep_c),
        grid=(steps,),
        in_specs=[streamed(gates), resident(n, hdim), resident(n, hdim),
                  resident(gates, hdim)],
        out_specs=tuple(streamed(hdim) for _ in stacks)
        + (carried(hdim), carried(hdim)),
        out_shape=stacks + (jax.ShapeDtypeStruct(h0.shape, h0.dtype),
                            jax.ShapeDtypeStruct(c0.shape, c0.dtype)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_scan_kernel_vmem(xproj, h0, c0, w_h2h)),
        interpret=interpret,
        name="lstm_cell_scan",
    )(xproj, h0, c0, w_h2h)
    return kept[0], (kept[1] if keep_c else None), hT, cT


def _scan_cells(xproj, h0, c0, w_h2h, reverse, impl, keep_c):
    """The forward over time. Returns the ``h`` of every step (T, N, H),
    the ``c`` of every step where the backward will want it, and the last
    step's ``(h, c)``. Where ``W_hh`` and a step's blocks fit in VMEM
    together, one kernel walks the steps with the weight held there; where
    they do not (and off the chip), ``lax.scan`` runs the cell a step at a
    time."""
    if impl != "jnp" and _scan_kernel_vmem(xproj, h0, c0,
                                           w_h2h) <= _VMEM_LIMIT:
        return _scan_cells_pallas(xproj, h0, c0, w_h2h, reverse, keep_c,
                                  interpret=(impl == "interpret"))

    def body(carry, xp):
        h, c = _cell(xp, *carry, w_h2h, impl)
        return (h, c), ((h, c) if keep_c else h)

    (hT, cT), kept = lax.scan(body, (h0, c0), xproj, reverse=reverse)
    hs, cs = kept if keep_c else (kept, None)
    return hs, cs, hT, cT


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _recurrence(xproj, h0, c0, w_h2h, reverse, impl):
    hs, _, hT, cT = _scan_cells(xproj, h0, c0, w_h2h, reverse, impl, False)
    return hs, hT, cT


def _recurrence_fwd(xproj, h0, c0, w_h2h, reverse, impl):
    hs, cs, hT, cT = _scan_cells(xproj, h0, c0, w_h2h, reverse, impl, True)
    return (hs, hT, cT), (xproj, h0, c0, hs, cs, w_h2h)


# float32 pre-activations of this many bytes are recomputed at a time: the
# backward's one large temporary (the forward's own stack of projections is
# as large). 256 rows of 4 x 1500 gates: 174 time steps
_PREACT_BYTES = 1 << 30


def _chunks(steps, rows, gates):
    """Equal runs of time steps, each within ``_PREACT_BYTES`` of float32
    pre-activations: [(start, stop)] in time order."""
    most = max(1, _PREACT_BYTES // (rows * gates * 4))
    n = -(-steps // most)
    size = -(-steps // n)
    return [(a, min(a + size, steps)) for a in range(0, steps, size)]


def _before(stack, first, a, b, reverse):
    """The states the steps ``a..b-1`` started from: the stack one step
    earlier in the scan's order, ``first`` before the scan's first step."""
    if reverse:
        if b < stack.shape[0]:
            return stack[a + 1:b + 1]
        return jnp.concatenate([stack[a + 1:], first[None]])
    if a:
        return stack[a - 1:b - 1]
    return jnp.concatenate([first[None], stack[:b - 1]])


def _dot(a, b, contract):
    # bf16 operands are exact on the MXU with f32 accumulation: see the
    # forward kernel on an ambient "highest"
    precision = lax.Precision.DEFAULT if b.dtype == jnp.bfloat16 else None
    return lax.dot_general(a, b, (contract, ((), ())), precision=precision,
                           preferred_element_type=jnp.float32)


def _backward_step(preact, c_prev, dh_out, w_t, dh, dc):
    """One time step of the reverse walk: the pointwise adjoints in
    float32, then the one product that waits for the step before it,
    ``dgates @ W_hh`` with both operands in the weight's dtype. ``w_t`` is
    ``W_hh^T`` (H, 4H), contracted over its last dimension as the forward
    contracts ``W_hh``: on the chip that is the weight as it already lies
    in memory, and the matmuls around the walk keep the layout they read
    it in (with ``W_hh`` itself the LM cell's step was 2.6% longer:
    PERF.md, PR 30)."""
    dgates, dc = _gate_adjoints(preact, c_prev,
                                dh + dh_out.astype(jnp.float32), dc)
    dgates = dgates.astype(w_t.dtype)
    return dgates, _dot(dgates, w_t, ((1,), (1,))), dc


def _backward_steps_jnp(preact, c_prev, dh_out, w_t, dh, dc, last_first):
    def step(carry, xs):
        dgates, dh, dc = _backward_step(*xs, w_t, *carry)
        return (dh, dc), dgates

    (dh, dc), dgates = lax.scan(step, (dh, dc), (preact, c_prev, dh_out),
                                reverse=last_first)
    return dgates, dh, dc


def _lstm_bwd_kernel(preact_ref, c_prev_ref, dh_out_ref, w_ref, dh_in_ref,
                     dc_in_ref, dgates_ref, dh_ref, dc_ref):
    """Grid step = time step, in the walk's order. ``W_hh`` and the
    carried ``(dh, dc)`` (the two whole-array outputs) stay in VMEM over
    the grid; a step's pre-activations, ``c_prev`` and output cotangent
    stream in and its ``dgates`` streams out beside the arithmetic."""
    _start_carry((dh_ref, dc_ref), (dh_in_ref, dc_in_ref))
    dgates_ref[0], dh_ref[:], dc_ref[:] = _backward_step(
        preact_ref[0], c_prev_ref[0], dh_out_ref[0], w_ref[:], dh_ref[:],
        dc_ref[:])


def _bwd_kernel_vmem(rows, hdim, w_dtype):
    return _walk_vmem(rows, hdim, w_dtype, [
        (4 * hdim, jnp.float32), (4 * hdim, w_dtype), (hdim, jnp.float32),
        (hdim, jnp.float32)])


def _backward_steps_pallas(preact, c_prev, dh_out, w_t, dh, dc, last_first,
                           interpret):
    steps, n, gates = preact.shape
    hdim = gates // 4
    streamed, resident, carried = _time_grid(steps, n, last_first)
    state = jax.ShapeDtypeStruct((n, hdim), jnp.float32)
    return pl.pallas_call(
        _lstm_bwd_kernel,
        grid=(steps,),
        in_specs=[streamed(gates), streamed(hdim), streamed(hdim),
                  resident(hdim, gates), resident(n, hdim),
                  resident(n, hdim)],
        out_specs=(streamed(gates), carried(hdim), carried(hdim)),
        out_shape=(jax.ShapeDtypeStruct(preact.shape, w_t.dtype), state,
                   state),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_bwd_kernel_vmem(n, hdim, w_t.dtype)),
        interpret=interpret,
        name="lstm_bwd_step",
    )(preact, c_prev, dh_out, w_t, dh, dc)


def _recurrence_bwd(reverse, impl, res, cts):
    xproj, h0, c0, hs, cs, w_h2h = res
    dhs, dhT, dcT = cts
    T, N, H = hs.shape
    operand = w_h2h.dtype    # what the forward's matmul ran in
    profiler.count("rnn.whole_backward_layers")
    if impl == "jnp" or _bwd_kernel_vmem(N, H, operand) > _VMEM_LIMIT:
        steps = _backward_steps_jnp
    else:
        steps = functools.partial(_backward_steps_pallas,
                                  interpret=(impl == "interpret"))

    hprev = _before(hs, h0, 0, T, reverse).astype(operand)
    dh, dc = dhT.astype(jnp.float32), dcT.astype(jnp.float32)
    chunks = _chunks(T, N, 4 * H)
    dgates = []
    # the scan's last steps first
    for a, b in (chunks if reverse else chunks[::-1]):
        preact = xproj[a:b].astype(jnp.float32) + _dot(
            hprev[a:b].reshape(-1, H), w_h2h, ((1,), (1,))
        ).reshape(b - a, N, 4 * H)
        part, dh, dc = steps(preact, _before(cs, c0, a, b, reverse),
                             dhs[a:b], w_h2h.T, dh, dc, not reverse)
        dgates.append(part)
    dgates = jnp.concatenate(dgates if reverse else dgates[::-1])
    dw = _dot(dgates.reshape(-1, 4 * H), hprev.reshape(-1, H), ((0,), (0,)))
    return (dgates.astype(xproj.dtype), dh.astype(h0.dtype),
            dc.astype(c0.dtype), dw.astype(w_h2h.dtype))


_recurrence.defvjp(_recurrence_fwd, _recurrence_bwd)


def lstm_recurrence(xproj, h0, c0, w_h2h, reverse=False, impl=None):
    """One direction of one LSTM layer over the input projection:
    (xproj (T,N,4H), h0 (N,H), c0 (N,H), w_h2h (4H,H)) -> (h of every step
    (T,N,H), last h, last c); ``reverse`` walks time backwards. ``impl`` as
    ``lstm_cell_fused``. Differentiable as a whole (module docstring)."""
    return _recurrence(xproj, h0, c0, w_h2h, bool(reverse), _resolved(impl))
