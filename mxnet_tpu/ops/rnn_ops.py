"""Fused recurrent ops: multi-layer (bi)directional RNN/LSTM/GRU via lax.scan.

Reference analogue: the ``RNN`` op (src/operator/rnn-inl.h, rnn.cc/.cu).
In the reference it is cuDNN-only — the CPU forward/backward are empty TODO
stubs (rnn-inl.h:123-153); this rebuild's version runs everywhere. The TPU
formulation: the input projection for the WHOLE sequence is one large matmul
(MXU-friendly, done outside the scan), and ``lax.scan`` carries only the
``h @ R^T`` recurrence. Gradients of ``gru`` / ``rnn_*`` come from jax.vjp
through the scan (its transpose); mode ``lstm`` runs
``ops.pallas.lstm.lstm_recurrence``, whose backward is written as a whole the
way cuDNN's is: what it keeps of the forward is ``(xproj, h0, c0, h and c of
every step, W_hh)``, its walk back over the time steps holds the pointwise
adjoints and the one matmul ``dgates_t @ W_hh``, and the gate recomputation
and ``dW_hh`` are one matmul each outside it.

Weight packing follows the reference's cuDNN convention (rnn_cell.py
FusedRNNCell.unpack_weights): all layer weights first — for each layer, each
direction: i2h (G*H, in) then h2h (G*H, H), row-major — followed by all
biases: per layer/direction i2h bias (G*H) then h2h bias (G*H).
Gate order: LSTM i,f,g,o ; GRU r,z,n (cuDNN order).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..base import AttrSpec, MXNetError
from .registry import register

_GATES = {"rnn_relu": 1, "rnn_tanh": 1, "lstm": 4, "gru": 3}


def _num_directions(bidirectional):
    return 2 if bidirectional else 1


def _layer_param_size(input_size, state_size, mode, bidirectional):
    G = _GATES[mode]
    D = _num_directions(bidirectional)
    return D * (G * state_size * (input_size + state_size)  # i2h + h2h
                + 2 * G * state_size)                        # two biases


def rnn_param_size(num_layers, input_size, state_size, mode,
                   bidirectional=False):
    """Total packed-parameter length (reference rnn-inl.h GetParamSize)."""
    D = _num_directions(bidirectional)
    size = _layer_param_size(input_size, state_size, mode, bidirectional)
    for _ in range(num_layers - 1):
        size += _layer_param_size(D * state_size, state_size, mode,
                                  bidirectional)
    return size


def _unpack(params, num_layers, input_size, state_size, mode, bidirectional):
    """Split the flat parameter vector into per-(layer, direction) pieces.

    Returns [(w_i2h, w_h2h, b_i2h, b_h2h)] indexed [layer][direction].
    """
    G = _GATES[mode]
    D = _num_directions(bidirectional)
    H = state_size
    weights, biases = [], []
    off = 0
    in_size = input_size
    for layer in range(num_layers):
        per_layer = []
        for d in range(D):
            w_i2h = params[off:off + G * H * in_size].reshape(G * H, in_size)
            off += G * H * in_size
            w_h2h = params[off:off + G * H * H].reshape(G * H, H)
            off += G * H * H
            per_layer.append([w_i2h, w_h2h])
        weights.append(per_layer)
        in_size = D * H
    for layer in range(num_layers):
        per_layer = []
        for d in range(D):
            b_i2h = params[off:off + G * H]
            off += G * H
            b_h2h = params[off:off + G * H]
            off += G * H
            per_layer.append([b_i2h, b_h2h])
        biases.append(per_layer)
    return [[tuple(weights[l][d]) + tuple(biases[l][d])
             for d in range(D)] for l in range(num_layers)]


def _cell_step(mode, H):
    """Returns step(carry, gates_in) for one timestep of a ``gru`` or
    ``rnn_*`` layer given precomputed x-projection + biases; carry is h."""
    if mode == "gru":
        def step(carry, xproj, w_h2h, b_h2h):
            (h,) = carry
            hproj = h @ w_h2h.T + b_h2h
            r = jax.nn.sigmoid(xproj[:, 0 * H:1 * H] + hproj[:, 0 * H:1 * H])
            z = jax.nn.sigmoid(xproj[:, 1 * H:2 * H] + hproj[:, 1 * H:2 * H])
            n = jnp.tanh(xproj[:, 2 * H:3 * H] + r * hproj[:, 2 * H:3 * H])
            h_new = (1 - z) * n + z * h
            return (h_new,), h_new
        return step
    act = jnp.tanh if mode == "rnn_tanh" else jax.nn.relu

    def step(carry, xproj, w_h2h):
        (h,) = carry
        h_new = act(xproj + h @ w_h2h.T)
        return (h_new,), h_new
    return step


def _run_direction(x, h0, c0, w_i2h, w_h2h, b_i2h, b_h2h, mode, H,
                   reverse=False):
    """One direction of one layer. x: (T, N, in). Returns (out(T,N,H), hT, cT)."""
    # whole-sequence input projection: one MXU matmul outside the scan
    T, N = x.shape[0], x.shape[1]
    # GRU keeps h2h bias separate (reset gate multiplies h-projection)
    bias = b_i2h if mode == "gru" else b_i2h + b_h2h
    # the product comes out in the dtype the sum with the bias will have
    # (float32 under bf16 weights and a float32 bias: the accumulator, not a
    # bf16 rounding of it), and the bias joins the (T, N, G*H) stack rather
    # than the flat product: the TPU compiler then writes the stack
    # time-major in one pass, as the recurrence reads it
    xproj = jnp.matmul(x.reshape(T * N, -1), w_i2h.T,
                       preferred_element_type=jnp.result_type(
                           x.dtype, w_i2h.dtype, bias.dtype)
                       ).reshape(T, N, -1) + bias
    # named, so a device trace tells the recurrence (and its backward) from
    # the projection matmuls around it
    with jax.named_scope("scan"):
        if mode == "lstm":
            # looked up at the call: the chip rehearsal steers its ``impl``
            from .pallas import lstm
            return lstm.lstm_recurrence(xproj, h0, c0, w_h2h, reverse=reverse)
        step = _cell_step(mode, H)
        rest = (w_h2h, b_h2h) if mode == "gru" else (w_h2h,)
        (hT,), out = lax.scan(lambda carry, xp: step(carry, xp, *rest),
                              (h0,), xproj, reverse=reverse)
    return out, hT, None


def _rnn_impl(rng, data, parameters, state, state_cell, state_size,
              num_layers, mode, bidirectional, p, _is_train):
    T, N, input_size = data.shape
    H = state_size
    D = _num_directions(bidirectional)
    if isinstance(parameters, (list, tuple)):
        # pre-split per-(layer, direction) pieces: the perf step runtime
        # (perf/step_runtime.py PackedRNNLayout) hoists the unpack to
        # parameter-layout time, so neither the forward slice/reshape of
        # the packed vector nor the backward gradient concat appears in
        # the step program — and the 2-D weight pieces are visible to the
        # mixed-precision cast (the flat vector is 1-D and never was)
        pieces = parameters
    else:
        pieces = _unpack(parameters, num_layers, input_size, H, mode,
                         bidirectional)
    x = data
    h_states, c_states = [], []
    for layer in range(num_layers):
        outs = []
        for d in range(D):
            w_i2h, w_h2h, b_i2h, b_h2h = pieces[layer][d]
            idx = layer * D + d
            h0 = state[idx]
            c0 = state_cell[idx] if mode == "lstm" else None
            with jax.named_scope(f"layer{layer}" + ("_reverse" if d else "")):
                out, hT, cT = _run_direction(
                    x, h0, c0, w_i2h, w_h2h, b_i2h, b_h2h, mode, H,
                    reverse=(d == 1))
            outs.append(out)
            h_states.append(hT)
            if mode == "lstm":
                c_states.append(cT)
        x = outs[0] if D == 1 else jnp.concatenate(outs, axis=-1)
        if p > 0 and _is_train and layer < num_layers - 1:
            rng, sub = jax.random.split(rng)
            keep = jax.random.bernoulli(sub, 1 - p, x.shape)
            x = jnp.where(keep, x / (1 - p), 0).astype(x.dtype)
    hy = jnp.stack(h_states)
    if mode == "lstm":
        return x, hy, jnp.stack(c_states)
    return x, hy, jnp.zeros_like(hy)


@register("_begin_state_zeros",
          attrs=AttrSpec(shape=("tuple",), batch_axis=("int", 0),
                         dtype=("str", "float32")))
def _begin_state_zeros(data, shape, batch_axis=0, dtype="float32"):
    """Zero initial RNN state whose batch dim (marked 0 in ``shape``) is
    taken from ``data``. Replaces the reference's backward shape inference
    of ``sym.zeros(shape=(0, H))`` begin states (rnn_cell.py:begin_state) —
    our inference is forward-only (jax.eval_shape), so the batch size is
    read off the input symbol instead."""
    out_shape = tuple(data.shape[batch_axis] if s == 0 else s for s in shape)
    return jnp.zeros(out_shape, jnp.dtype(dtype))


def _rnn_nout(attrs):
    if attrs.get("state_outputs") in (True, "True", "1"):
        return 3 if attrs.get("mode") == "lstm" else 2
    return 1


def _rnn_param_shapes(attrs, shapes):
    d = shapes[0]
    H = int(attrs["state_size"])
    L = int(attrs["num_layers"])
    bi = attrs.get("bidirectional") in (True, "True", "1")
    D = 2 if bi else 1
    mode = attrs.get("mode", "lstm")
    psize = rnn_param_size(L, d[2], H, mode, bi)
    st = (L * D, d[1], H)
    out = [d, (psize,), st]
    if mode == "lstm":
        out.append(st)
    return out


def _rnn_uses_rng(attrs):
    """Inter-layer dropout is the RNN op's only randomness: with p=0 the
    graph is deterministic and the executor's per-step key split/fold is
    skipped entirely (the signature still takes a key, unused)."""
    try:
        return float(attrs.get("p", 0.0) or 0.0) > 0.0
    except (TypeError, ValueError):
        return True


@register("RNN",
          num_inputs=None,
          input_names=["data", "parameters", "state", "state_cell"],
          num_outputs=_rnn_nout,
          needs_rng=_rnn_uses_rng,
          needs_is_train=True,
          param_shapes=_rnn_param_shapes,
          attrs=AttrSpec(state_size=("int",), num_layers=("int",),
                         mode=("str", "lstm"),
                         bidirectional=("bool", False),
                         p=("float", 0.0),
                         state_outputs=("bool", False),
                         lstm_state_clip_min=("any", None),
                         lstm_state_clip_max=("any", None)))
def _rnn(rng, *inputs, state_size, num_layers, mode="lstm",
         bidirectional=False, p=0.0, state_outputs=False,
         lstm_state_clip_min=None, lstm_state_clip_max=None,
         _is_train=False):
    """Fused multi-layer RNN (reference rnn-inl.h; cuDNN-equivalent)."""
    if mode not in _GATES:
        raise MXNetError(f"unknown RNN mode {mode}")
    if mode == "lstm":
        if len(inputs) != 4:
            raise MXNetError("lstm mode needs data, parameters, state, "
                             "state_cell")
        data, parameters, state, state_cell = inputs
    else:
        if len(inputs) != 3:
            raise MXNetError(f"{mode} mode needs data, parameters, state")
        data, parameters, state = inputs
        state_cell = None
    out, hy, cy = _rnn_impl(rng, data, parameters, state, state_cell,
                            state_size, num_layers, mode, bidirectional,
                            p, _is_train)
    # hidden outputs are always produced; the registry's num_outputs picks
    # the visible prefix (out [, hy [, cy]])
    return out, hy, cy
