"""Fused LSTM cell: one Pallas kernel per scan step.

The jnp cell (rnn_ops._cell_step) emits a matmul plus ~10 pointwise ops
per step that XLA fuses only partially across the scan boundary; this
kernel does the h-projection on the MXU and all four gate nonlinearities +
state update in a single VPU pass over VMEM-resident blocks. Backward is a
hand-written VJP (the standard LSTM cell adjoints, computed in jnp — they
are one matmul + pointwise, and autodiff can't see through pallas_call).
Gate order i,f,g,o matches the RNN op's cuDNN packing (rnn_ops.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl


def _gates(xproj, h, w_h2h):
    g = xproj.astype(jnp.float32) + jax.lax.dot_general(
        h.astype(jnp.float32), w_h2h.astype(jnp.float32),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    H = h.shape[-1]
    return (jax.nn.sigmoid(g[:, 0 * H:1 * H]),
            jax.nn.sigmoid(g[:, 1 * H:2 * H]),
            jnp.tanh(g[:, 2 * H:3 * H]),
            jax.nn.sigmoid(g[:, 3 * H:4 * H]))


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


def _cell_bwd(impl, res, cts):
    xproj, h, c, w_h2h = res
    dh_new, dc_new = cts
    i, f, g, o = _gates(xproj, h, w_h2h)  # rematerialize (cheap pointwise)
    cf = c.astype(jnp.float32)
    c_new = f * cf + i * g
    tc = jnp.tanh(c_new)
    dh32 = dh_new.astype(jnp.float32)
    dc = dc_new.astype(jnp.float32) + dh32 * o * (1 - tc * tc)
    d_i = dc * g * i * (1 - i)
    d_f = dc * cf * f * (1 - f)
    d_g = dc * i * (1 - g * g)
    d_o = dh32 * tc * o * (1 - o)
    dgates = jnp.concatenate([d_i, d_f, d_g, d_o], axis=-1)
    dxproj = dgates.astype(xproj.dtype)
    dh = (dgates @ w_h2h.astype(jnp.float32)).astype(h.dtype)
    dc_prev = (dc * f).astype(c.dtype)
    dw = jax.lax.dot_general(dgates, h.astype(jnp.float32),
                             (((0,), (0,)), ((), ()))).astype(w_h2h.dtype)
    return dxproj, dh, dc_prev, dw


_cell.defvjp(_cell_fwd, _cell_bwd)


def lstm_cell_fused(xproj, h, c, w_h2h, impl=None):
    """One LSTM step: (xproj (N,4H), h (N,H), c (N,H), w_h2h (4H,H)) ->
    (h', c'). impl: None = the compiled Pallas kernel on a TPU backend,
    the jnp cell elsewhere; 'pallas' | 'interpret' | 'jnp' to force. On
    TPU the kernel either compiles or raises — it never gives way to the
    jnp cell on its own."""
    if impl is None:
        impl = "pallas" if jax.default_backend() == "tpu" else "jnp"
    return _cell(xproj, h, c, w_h2h, impl)
