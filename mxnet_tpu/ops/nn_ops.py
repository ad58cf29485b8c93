"""Neural-network layer operators.

Reference surface: the legacy layer ops under src/operator/ —
fully_connected.cc:76, convolution.cc:176, deconvolution.cc, pooling.cc,
batch_norm.cc:420, activation.cc, leaky_relu.cc, dropout.cc, lrn.cc,
instance_norm.cc, softmax_activation.cc, softmax_output.cc, svm_output.cc,
regression_output.cc, loss_binary_op.cc, upsampling.cc — rebuilt as
jnp/lax compositions. Convs/matmuls hit the MXU via lax.conv_general_dilated
and jnp.dot; loss layers with implicit gradients (SoftmaxOutput & friends) use
jax.custom_vjp to reproduce the reference's "backward ignores head grad"
semantics.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ..base import AttrSpec, MXNetError
from .registry import OP_TABLE, register

# ---------------------------------------------------------------------------
# FullyConnected (fully_connected.cc:76)
# ---------------------------------------------------------------------------


def _fc_param_shapes(attrs, shapes):
    d = shapes[0]
    nh = int(attrs["num_hidden"])
    in_dim = 1
    if attrs.get("flatten", True):
        for s in d[1:]:
            in_dim *= s
    else:
        in_dim = d[-1]
    out = [d, (nh, in_dim)]
    if len(shapes) > 2:
        out.append((nh,))
    return out


@register("FullyConnected",
          num_inputs=None, input_names=["data", "weight", "bias"],
          param_shapes=_fc_param_shapes,
          attrs=AttrSpec(num_hidden=("int",), no_bias=("bool", False),
                         flatten=("bool", True)))
def _fully_connected(*args, num_hidden, no_bias=False, flatten=True):
    data, weight = args[0], args[1]
    if flatten and data.ndim > 2:
        data = data.reshape(data.shape[0], -1)
    # compute in the activation dtype (mixed precision: bf16 activations
    # keep the matmul on the MXU even when master weights are fp32)
    if weight.dtype != data.dtype:
        weight = weight.astype(data.dtype)
    out = jnp.dot(data, weight.T)
    if not no_bias:
        out = out + args[2].astype(data.dtype)
    return out


# ---------------------------------------------------------------------------
# Convolution / Deconvolution (convolution.cc:176, deconvolution.cc)
# ---------------------------------------------------------------------------

_CONV_SPEC = AttrSpec(
    kernel=("tuple",), stride=("tuple", ()), dilate=("tuple", ()),
    pad=("tuple", ()), num_filter=("int",), num_group=("int", 1),
    workspace=("int", 1024), no_bias=("bool", False),
    cudnn_tune=("str", None), cudnn_off=("bool", False),
    layout=("str", None), adj=("tuple", ()), target_shape=("tuple", ()),
)


def _conv_dims(ndim_spatial, layout):
    if layout is None or layout in ("None",):
        layout = {1: "NCW", 2: "NCHW", 3: "NCDHW"}[ndim_spatial]
    if layout in ("NCW", "NCHW", "NCDHW"):
        spatial = layout[2:]
        return layout, "OI" + spatial, layout
    if layout in ("NWC", "NHWC", "NDHWC"):
        spatial = layout[1:-1]
        return layout, "O" + spatial + "I", layout
    raise MXNetError(f"unsupported conv layout {layout}")


def _norm_spatial(t, n, default):
    t = tuple(t) if t else ()
    return t if len(t) == n else (default,) * n


def _conv_param_shapes(attrs, shapes):
    d = shapes[0]
    nf = int(attrs["num_filter"])
    g = int(attrs.get("num_group", 1) or 1)
    kernel = attrs["kernel"]
    layout = attrs.get("layout")
    c_axis = 1 if (layout in (None, "None") or str(layout).startswith("NC")) else len(d) - 1
    if str(layout).startswith("NC") or layout in (None, "None"):
        w = (nf, d[c_axis] // g) + tuple(kernel)
    else:
        w = (nf,) + tuple(kernel) + (d[c_axis] // g,)
    out = [d, w]
    if len(shapes) > 2:
        out.append((nf,))
    return out


@register("Convolution",
          num_inputs=None, input_names=["data", "weight", "bias"],
          param_shapes=_conv_param_shapes,
          attrs=_CONV_SPEC)
def _convolution(*args, kernel, stride=(), dilate=(), pad=(), num_filter=0,
                 num_group=1, workspace=1024, no_bias=False, cudnn_tune=None,
                 cudnn_off=False, layout=None, adj=(), target_shape=()):
    data, weight = args[0], args[1]
    nsp = len(kernel)
    stride = _norm_spatial(stride, nsp, 1)
    dilate = _norm_spatial(dilate, nsp, 1)
    pad = _norm_spatial(pad, nsp, 0)
    if weight.dtype != data.dtype:  # mixed precision: compute in act dtype
        weight = weight.astype(data.dtype)
    lhs_spec, rhs_spec, out_spec = _conv_dims(nsp, layout)
    dn = lax.conv_dimension_numbers(data.shape, weight.shape,
                                    (lhs_spec, rhs_spec, out_spec))
    out = lax.conv_general_dilated(
        data, weight,
        window_strides=stride,
        padding=[(p, p) for p in pad],
        rhs_dilation=dilate,
        dimension_numbers=dn,
        feature_group_count=num_group,
        # no preferred_element_type: the TPU MXU accumulates bf16 convs in
        # fp32 natively, and an explicit fp32 output breaks the conv
        # transpose rule under vjp (bf16 weight vs fp32 cotangent)
    )
    if out.dtype != data.dtype:
        out = out.astype(data.dtype)
    if not no_bias:
        bias = args[2].astype(out.dtype)
        c_axis = out_spec.index("C")
        bshape = [1] * out.ndim
        bshape[c_axis] = bias.shape[0]
        out = out + bias.reshape(bshape)
    return out


def _deconv_param_shapes(attrs, shapes):
    d = shapes[0]
    nf = int(attrs["num_filter"])
    g = int(attrs.get("num_group", 1) or 1)
    out = [d, (d[1], nf // g) + tuple(attrs["kernel"])]
    if len(shapes) > 2:
        out.append((nf,))
    return out


@register("Deconvolution",
          num_inputs=None, input_names=["data", "weight", "bias"],
          param_shapes=_deconv_param_shapes,
          attrs=_CONV_SPEC)
def _deconvolution(*args, kernel, stride=(), dilate=(), pad=(), num_filter=0,
                   num_group=1, workspace=1024, no_bias=False, cudnn_tune=None,
                   cudnn_off=False, layout=None, adj=(), target_shape=()):
    data, weight = args[0], args[1]
    nsp = len(kernel)
    stride = _norm_spatial(stride, nsp, 1)
    dilate = _norm_spatial(dilate, nsp, 1)
    pad = _norm_spatial(pad, nsp, 0)
    adj = _norm_spatial(adj, nsp, 0)
    # deconv weight layout is (C_in, C_out/g, *kernel); build the equivalent
    # forward-conv weight (C_out, C_in/g, *k) with spatially flipped taps
    cin, coutg = weight.shape[0], weight.shape[1]
    g = num_group
    w = weight.reshape((g, cin // g, coutg) + weight.shape[2:])
    w = jnp.swapaxes(w, 1, 2)  # (g, C_out/g, C_in/g, *k)
    w = w.reshape((g * coutg, cin // g) + weight.shape[2:])
    w = jnp.flip(w, axis=tuple(range(2, 2 + nsp)))
    lhs_spec, rhs_spec, out_spec = _conv_dims(nsp, None)
    dn = lax.conv_dimension_numbers(data.shape, w.shape,
                                    (lhs_spec, rhs_spec, out_spec))
    dk = tuple((k - 1) * d + 1 for k, d in zip(kernel, dilate))
    out = lax.conv_general_dilated(
        data, w,
        window_strides=(1,) * nsp,
        padding=[(dk_i - 1 - p, dk_i - 1 - p + a)
                 for dk_i, p, a in zip(dk, pad, adj)],
        lhs_dilation=stride,
        rhs_dilation=dilate,
        dimension_numbers=dn,
        feature_group_count=num_group,
    )
    if not no_bias:
        bias = args[2]
        out = out + bias.reshape((1, -1) + (1,) * nsp)
    return out


# ---------------------------------------------------------------------------
# Pooling (pooling.cc, pool.h) via lax.reduce_window
# ---------------------------------------------------------------------------


@register("Pooling",
          attrs=AttrSpec(kernel=("tuple", ()), pool_type=("str", "max"),
                         global_pool=("bool", False),
                         pooling_convention=("str", "valid"),
                         stride=("tuple", ()), pad=("tuple", ()),
                         cudnn_off=("bool", False), layout=("str", None)))
def _pooling(data, kernel=(), pool_type="max", global_pool=False,
             pooling_convention="valid", stride=(), pad=(), cudnn_off=False,
             layout=None):
    nsp = data.ndim - 2
    # channel-last layouts (NWC/NHWC/NDHWC) keep spatial dims at 1..ndim-2 —
    # the TPU-native layout; default (None/NC*) matches the reference's NCHW
    channel_last = layout is not None and str(layout).endswith("C") \
        and not str(layout).startswith("NC")
    sp0 = 1 if channel_last else 2
    if global_pool:
        kernel = data.shape[sp0:sp0 + nsp]
        stride = (1,) * nsp
        pad = (0,) * nsp
    stride = _norm_spatial(stride, nsp, 1)
    pad = _norm_spatial(pad, nsp, 0)
    if channel_last:
        window = (1,) + tuple(kernel) + (1,)
        strides = (1,) + tuple(stride) + (1,)
        padding = [(0, 0)] + [(p, p) for p in pad] + [(0, 0)]
    else:
        window = (1, 1) + tuple(kernel)
        strides = (1, 1) + tuple(stride)
        padding = [(0, 0), (0, 0)] + [(p, p) for p in pad]
    if pooling_convention == "full" and not global_pool:
        # reference 'full' uses ceil for the output size: pad extra on the
        # high side so VALID reduce_window produces the ceil size
        import math
        for i in range(nsp):
            size = data.shape[sp0 + i] + 2 * pad[i]
            out_full = int(math.ceil((size - kernel[i]) / stride[i])) + 1
            needed = (out_full - 1) * stride[i] + kernel[i] - size
            lo, hi = padding[sp0 + i]
            padding[sp0 + i] = (lo, hi + max(0, needed))
    if pool_type == "max":
        init = -jnp.inf if jnp.issubdtype(data.dtype, jnp.floating) else jnp.iinfo(data.dtype).min
        out = lax.reduce_window(data, init, lax.max, window, strides, padding)
    elif pool_type in ("avg", "sum"):
        out = lax.reduce_window(data, 0.0, lax.add, window, strides, padding)
        if pool_type == "avg":
            out = out / float(functools.reduce(lambda a, b: a * b, kernel, 1))
    else:
        raise MXNetError(f"unknown pool_type {pool_type}")
    return out.astype(data.dtype)


@register("UpSampling", key_var_num_args="num_args",
          num_inputs=None,
          attrs=AttrSpec(scale=("int",), num_filter=("int", 0),
                         sample_type=("str",), multi_input_mode=("str", "concat"),
                         num_args=("int", 1), workspace=("int", 512)))
def _upsampling(*args, scale, num_filter=0, sample_type="nearest",
                multi_input_mode="concat", num_args=1, workspace=512):
    def up(x):
        return jnp.repeat(jnp.repeat(x, scale, axis=2), scale, axis=3)
    if sample_type == "nearest":
        outs = [up(a) for a in args]
        if len(outs) == 1:
            return outs[0]
        if multi_input_mode == "sum":
            return sum(outs)
        return jnp.concatenate(outs, axis=1)
    if sample_type == "bilinear":
        x = args[0]
        n, c, h, w = x.shape
        return jax.image.resize(x, (n, c, h * scale, w * scale), method="bilinear")
    raise MXNetError(f"unknown sample_type {sample_type}")


# ---------------------------------------------------------------------------
# Normalization layers
# ---------------------------------------------------------------------------


def _bn_nout(attrs):
    return 3 if attrs.get("output_mean_var") in (True, "True", "1") else 1


def _bn_param_shapes(attrs, shapes):
    d = shapes[0]
    axis = int(attrs.get("axis", 1) or 1) % len(d)
    c = (d[axis],)
    return [d, c, c, c, c]


@register("BatchNorm",
          num_inputs=5,
          input_names=["data", "gamma", "beta", "moving_mean", "moving_var"],
          num_outputs=_bn_nout,
          needs_is_train=True,
          aux_inputs=(3, 4),
          param_shapes=_bn_param_shapes,
          aux_update={1: 3, 2: 4},  # written back into moving_mean/var
          attrs=AttrSpec(eps=("float", 1e-3), momentum=("float", 0.9),
                         fix_gamma=("bool", True),
                         use_global_stats=("bool", False),
                         output_mean_var=("bool", False),
                         axis=("int", 1), cudnn_off=("bool", False)))
def _batch_norm(data, gamma, beta, moving_mean, moving_var, eps=1e-3,
                momentum=0.9, fix_gamma=True, use_global_stats=False,
                output_mean_var=False, axis=1, cudnn_off=False,
                _is_train=False):
    axis = axis % data.ndim
    reduce_axes = tuple(i for i in range(data.ndim) if i != axis)
    bshape = [1] * data.ndim
    bshape[axis] = data.shape[axis]

    if _is_train and not use_global_stats:
        x32 = data.astype(jnp.float32)
        if data.dtype in (jnp.bfloat16, jnp.float16):
            # low-precision compute path: one-pass sufficient statistics —
            # sum and sum-of-squares reduce in a single multi-output
            # fusion (ONE HBM read of the activation where mean-then-var
            # reads it twice; worth ~11% on the ResNet-50 train step, see
            # docs/how_to/performance.md). fp32 accumulators lose nothing relative to
            # 8-bit-mantissa data, so E[x^2]-E[x]^2 is safe here.
            n = 1
            for i in reduce_axes:
                n *= data.shape[i]
            s1 = jnp.sum(x32, axis=reduce_axes)
            s2 = jnp.sum(lax.square(x32), axis=reduce_axes)
            mean = s1 / n
            var = jnp.maximum(s2 / n - lax.square(mean), 0.0)
        else:
            # fp32 path: centered two-pass keeps the ~3 digits the
            # difference-of-squares form loses on nonzero-mean fp32
            # activations (gradients through var inherit the loss).
            # NB stats are fp32 regardless of input dtype (x32 above) —
            # fp64 inputs get fp32 statistics, like the rest of the op.
            mean = jnp.mean(x32, axis=reduce_axes)
            var = jnp.var(x32, axis=reduce_axes)
        new_mean = momentum * moving_mean + (1 - momentum) * mean
        new_var = momentum * moving_var + (1 - momentum) * var
    else:
        mean, var = moving_mean, moving_var
        new_mean, new_var = moving_mean, moving_var
    g = jnp.ones_like(gamma) if fix_gamma else gamma
    inv = lax.rsqrt(var + eps)
    out = (data - mean.reshape(bshape).astype(data.dtype)) \
        * (g * inv).reshape(bshape).astype(data.dtype) \
        + beta.reshape(bshape).astype(data.dtype)
    # always return the aux updates; the invoke layer writes them back in
    # train mode and drops them otherwise (visible outputs = _bn_nout)
    return (out, lax.stop_gradient(new_mean), lax.stop_gradient(new_var))


@register("InstanceNorm",
          num_inputs=3, input_names=["data", "gamma", "beta"],
          param_shapes=lambda attrs, shapes: [shapes[0], (shapes[0][1],),
                                              (shapes[0][1],)],
          attrs=AttrSpec(eps=("float", 1e-3)))
def _instance_norm(data, gamma, beta, eps=1e-3):
    axes = tuple(range(2, data.ndim))
    mean = jnp.mean(data, axis=axes, keepdims=True)
    var = jnp.var(data, axis=axes, keepdims=True)
    bshape = (1, -1) + (1,) * (data.ndim - 2)
    return (data - mean) * lax.rsqrt(var + eps) * gamma.reshape(bshape) \
        + beta.reshape(bshape)


@register("RMSNorm", num_inputs=2, input_names=["data", "gamma"],
          param_shapes=lambda attrs, shapes: [shapes[0], (shapes[0][-1],)],
          attrs=AttrSpec(eps=("float", 1e-6)))
def _rms_norm(data, gamma, eps=1e-6):
    """x / sqrt(mean(x^2) + eps) * gamma over the last axis, computed in
    float32 and returned in the input's dtype."""
    x = data.astype(jnp.float32)
    y = x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return (y * gamma.astype(jnp.float32)).astype(data.dtype)


def _gated_ffn_param_shapes(attrs, shapes):
    d, f = shapes[0][-1], int(attrs["num_hidden"])
    return [shapes[0], (f, d), (f, d), (d, f)]


def gated_ffn(x, w_gate, w_up, w_down):
    """SwiGLU: (silu(x W_gate^T) * (x W_up^T)) W_down^T, weights (out, in)
    as ``FullyConnected`` keeps them, matmuls in the activation's dtype."""
    w_gate, w_up, w_down = (w.astype(x.dtype)
                            for w in (w_gate, w_up, w_down))
    hidden = jax.nn.silu(jnp.dot(x, w_gate.T)) * jnp.dot(x, w_up.T)
    return jnp.dot(hidden, w_down.T)


@register("GatedFFN", num_inputs=4,
          input_names=["data", "gate_weight", "up_weight", "down_weight"],
          param_shapes=_gated_ffn_param_shapes,
          attrs=AttrSpec(num_hidden=("int",)))
def _gated_ffn(data, gate_weight, up_weight, down_weight, num_hidden):
    """Gated feed-forward (SwiGLU) over the last axis, no bias."""
    return gated_ffn(data, gate_weight, up_weight, down_weight)


# ---------------------------------------------------------------------------
# ShortConv: the gated short convolution of hybrid convolution-attention
# decoders (a causal depthwise convolution of a few taps between two gates)
# ---------------------------------------------------------------------------


def _shift(x, steps):
    """``x`` (B, S, D) moved ``steps`` positions later along S (earlier for
    a negative count), zeros entering at the row's end it leaves: a row
    never reads its neighbour."""
    if steps == 0:
        return x
    s = x.shape[1]
    if steps > 0:
        return jnp.pad(x, ((0, 0), (steps, 0), (0, 0)))[:, :s]
    return jnp.pad(x, ((0, 0), (0, -steps), (0, 0)))[:, -steps:]


def _taps(g, w, sign):
    """sum_k w[:, k] * g moved ``sign * (L - 1 - k)`` positions: the causal
    cross-correlation for +1, its transpose for -1."""
    taps = w.shape[-1]
    return sum(_shift(g, sign * (taps - 1 - k)) * w[:, k]
               for k in range(taps))


@jax.custom_vjp
def gated_short_conv(b, c, h, w):
    """``c * conv(b * h)`` over (B, S, D) streams: ``g = b * h``, ``y_t =
    sum_k w[:, k] g_{t - (L - 1 - k)}`` a channel with ``g_s = 0`` for
    ``s < 0`` in every row (a causal depthwise cross-correlation, left
    padding L - 1, no bias), then the second gate. Arithmetic in float32,
    result in the streams' dtype. The residuals are the four inputs: the
    backward makes ``g`` and ``y`` again in its one pass over ``b``, ``c``,
    ``h`` and the cotangent, and keeps no float32 stream between the two."""
    return _gated_short_conv_fwd(b, c, h, w)[0]


def _gated_short_conv_fwd(b, c, h, w):
    f32 = jnp.float32
    y = _taps(b.astype(f32) * h.astype(f32), w.astype(f32), 1)
    return (c.astype(f32) * y).astype(b.dtype), (b, c, h, w)


def _gated_short_conv_bwd(res, dz):
    b, c, h, w = res
    f32 = jnp.float32
    bf, cf, hf, wf = (v.astype(f32) for v in (b, c, h, w))
    dz = dz.astype(f32)
    g = bf * hf
    dy = dz * cf
    dc = dz * _taps(g, wf, 1)
    dg = _taps(dy, wf, -1)
    taps = w.shape[-1]
    dw = jnp.stack([jnp.sum(dy * _shift(g, taps - 1 - k), axis=(0, 1))
                    for k in range(taps)], axis=-1)
    return ((dg * hf).astype(b.dtype), dc.astype(c.dtype),
            (dg * bf).astype(h.dtype), dw.astype(w.dtype))


gated_short_conv.defvjp(_gated_short_conv_fwd, _gated_short_conv_bwd)


def _short_conv_param_shapes(attrs, shapes):
    d = shapes[0][-1]
    return [shapes[0], (3 * d, d), (d, int(attrs["kernel"])), (d, d)]


@register("ShortConv", num_inputs=4,
          input_names=["data", "in_weight", "conv_weight", "out_weight"],
          param_shapes=_short_conv_param_shapes,
          attrs=AttrSpec(kernel=("int", 3)))
def _short_conv(data, in_weight, conv_weight, out_weight, kernel=3):
    """Gated short convolution over (B, S, D), no bias, no activation:
    ``[b, c, h] = split(data W_in^T, 3)`` with ``in_weight`` (3D, D);
    ``y = conv(b * h)``, a causal depthwise convolution of ``kernel`` taps
    with ``conv_weight`` (D, kernel), every row of the batch starting from
    zeros; ``out = (c * y) W_out^T`` with ``out_weight`` (D, D). The two
    matmuls run in the activation's dtype; what lies between them is
    memory-bound (three streams read, one written) and carries the named
    scope ``conv`` beside ``in_proj`` and ``out_proj``, so a device trace
    tells the three apart."""
    d = data.shape[-1]
    if data.ndim != 3 or conv_weight.shape != (d, kernel):
        raise MXNetError(
            f"ShortConv: data {data.shape} is (rows, positions, channels) "
            f"and conv_weight {conv_weight.shape} (channels, {kernel})")
    with jax.named_scope("in_proj"):
        bch = jnp.dot(data, in_weight.astype(data.dtype).T)
    with jax.named_scope("conv"):
        z = gated_short_conv(bch[..., :d], bch[..., d:2 * d], bch[..., 2 * d:],
                             conv_weight)
    with jax.named_scope("out_proj"):
        return jnp.dot(z, out_weight.astype(data.dtype).T)


@register("LRN", attrs=AttrSpec(alpha=("float", 1e-4), beta=("float", 0.75),
                                knorm=("float", 2.0), nsize=("int",),
                                axis=("int", 1)))
def _lrn(data, alpha=1e-4, beta=0.75, knorm=2.0, nsize=5, axis=1):
    # ``axis`` is a TPU-build extension: the reference normalizes over the
    # NCHW channel axis 1 only; NHWC models pass axis=-1
    axis = axis % data.ndim
    sq = jnp.square(data)
    half = nsize // 2
    pad = [(half, half) if i == axis else (0, 0) for i in range(data.ndim)]
    sq = jnp.pad(sq, pad)
    window = tuple(nsize if i == axis else 1 for i in range(data.ndim))
    ssum = lax.reduce_window(sq, 0.0, lax.add, window, (1,) * data.ndim,
                             [(0, 0)] * data.ndim)
    return data / jnp.power(knorm + alpha / nsize * ssum, beta)


# ---------------------------------------------------------------------------
# Activations
# ---------------------------------------------------------------------------


@register("Activation", attrs=AttrSpec(act_type=("str",)))
def _activation(data, act_type):
    if act_type == "relu":
        return jnp.maximum(data, 0)
    if act_type == "sigmoid":
        return jax.nn.sigmoid(data)
    if act_type == "tanh":
        return jnp.tanh(data)
    if act_type == "softrelu":
        return jax.nn.softplus(data)
    if act_type == "softsign":
        return data / (1 + jnp.abs(data))
    if act_type == "silu":
        return jax.nn.silu(data)
    raise MXNetError(f"unknown act_type {act_type}")


def _lrelu_param_shapes(attrs, shapes):
    if len(shapes) == 1:
        return list(shapes)
    return [shapes[0], (shapes[0][1],)]


@register("LeakyReLU",
          num_inputs=None, input_names=["data", "gamma"],
          param_shapes=_lrelu_param_shapes,
          needs_rng=True, needs_is_train=True,
          attrs=AttrSpec(act_type=("str", "leaky"), slope=("float", 0.25),
                         lower_bound=("float", 0.125),
                         upper_bound=("float", 0.334)))
def _leaky_relu(rng, *args, act_type="leaky", slope=0.25, lower_bound=0.125,
                upper_bound=0.334, _is_train=False):
    data = args[0]
    if act_type == "leaky":
        return jnp.where(data > 0, data, slope * data)
    if act_type == "elu":
        return jnp.where(data > 0, data, slope * jnp.expm1(data))
    if act_type == "prelu":
        gamma = args[1]
        bshape = (1, -1) + (1,) * (data.ndim - 2)
        return jnp.where(data > 0, data, gamma.reshape(bshape) * data)
    if act_type == "rrelu":
        if _is_train:
            s = jax.random.uniform(rng, data.shape, data.dtype,
                                   lower_bound, upper_bound)
        else:
            s = (lower_bound + upper_bound) / 2.0
        return jnp.where(data > 0, data, s * data)
    raise MXNetError(f"unknown LeakyReLU act_type {act_type}")


@register("Dropout", needs_rng=True, needs_is_train=True,
          attrs=AttrSpec(p=("float", 0.5), mode=("str", "training")))
def _dropout(rng, data, p=0.5, mode="training", _is_train=False):
    if (not _is_train and mode != "always") or p <= 0:
        return data
    keep = 1.0 - p
    mask = jax.random.bernoulli(rng, keep, data.shape)
    return jnp.where(mask, data / keep, 0).astype(data.dtype)


@register("softmax", attrs=AttrSpec(axis=("int", -1),
                                    temperature=("any", None)))
def _softmax(data, axis=-1, temperature=None):
    if temperature not in (None, "None"):
        data = data / float(temperature)
    return jax.nn.softmax(data, axis=axis)


@register("log_softmax", attrs=AttrSpec(axis=("int", -1),
                                        temperature=("any", None)))
def _log_softmax(data, axis=-1, temperature=None):
    if temperature not in (None, "None"):
        data = data / float(temperature)
    return jax.nn.log_softmax(data, axis=axis)


@register("SoftmaxActivation", attrs=AttrSpec(mode=("str", "instance")))
def _softmax_activation(data, mode="instance"):
    if mode == "channel":
        return jax.nn.softmax(data, axis=1)
    return jax.nn.softmax(data.reshape(data.shape[0], -1), axis=-1).reshape(data.shape)


# ---------------------------------------------------------------------------
# Output/loss layers with implicit gradients. The reference's backward for
# these ignores the incoming head gradient (they are terminal loss layers —
# softmax_output.cc, regression_output.cc); custom_vjp reproduces that.
# ---------------------------------------------------------------------------


def _softmax_out_fwd(data, label, grad_scale, ignore_label, multi_output,
                     use_ignore, preserve_shape, normalization, out_grad,
                     smooth_alpha=0.0):
    if multi_output:
        prob = jax.nn.softmax(data, axis=1)
    elif preserve_shape:
        prob = jax.nn.softmax(data, axis=-1)
    else:
        prob = jax.nn.softmax(data.reshape(data.shape[0], -1), axis=-1)
        prob = prob.reshape(data.shape)
    return prob


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6, 7, 8))
def _softmax_output_core(data, label, grad_scale, ignore_label, multi_output,
                         use_ignore, preserve_shape, normalization, out_grad):
    return _softmax_out_fwd(data, label, grad_scale, ignore_label, multi_output,
                            use_ignore, preserve_shape, normalization, out_grad)


def _softmax_output_fwd(data, label, grad_scale, ignore_label, multi_output,
                        use_ignore, preserve_shape, normalization, out_grad):
    prob = _softmax_out_fwd(data, label, grad_scale, ignore_label, multi_output,
                            use_ignore, preserve_shape, normalization, out_grad)
    return prob, (prob, label)


def _softmax_output_bwd(grad_scale, ignore_label, multi_output, use_ignore,
                        preserve_shape, normalization, out_grad, res, g):
    prob, label = res
    class_axis = 1 if multi_output else prob.ndim - 1
    nclass = prob.shape[class_axis]
    lab = label.astype(jnp.int32)
    onehot = jax.nn.one_hot(lab, nclass, dtype=prob.dtype)
    if multi_output:
        # label (N, *spatial); move the class axis of onehot to axis 1
        onehot = jnp.moveaxis(onehot, -1, 1)
    grad = prob - onehot
    if use_ignore:
        mask = (lab != int(ignore_label)).astype(prob.dtype)
        mask = jnp.expand_dims(mask, class_axis)
        grad = grad * mask
    scale = grad_scale
    if normalization == "batch":
        scale = scale / prob.shape[0]
    elif normalization == "valid" and use_ignore:
        valid = jnp.maximum(jnp.sum(lab != int(ignore_label)), 1)
        grad = grad / valid.astype(prob.dtype)
    if out_grad:
        grad = grad * g
    return (grad * scale, jnp.zeros_like(label))


_softmax_output_core.defvjp(_softmax_output_fwd, _softmax_output_bwd)


def _softmax_out_label_shape(attrs, shapes):
    d = shapes[0]
    if attrs.get("multi_output"):
        lab = (d[0],) + tuple(d[2:])
    elif attrs.get("preserve_shape"):
        lab = tuple(d[:-1])
    else:
        lab = (d[0],)
    return [d, lab]


@register("SoftmaxOutput", aliases=["Softmax"],
          param_shapes=_softmax_out_label_shape,
          num_inputs=2, input_names=["data", "label"],
          attrs=AttrSpec(grad_scale=("float", 1.0), ignore_label=("float", -1.0),
                         multi_output=("bool", False), use_ignore=("bool", False),
                         preserve_shape=("bool", False),
                         normalization=("str", "null"), out_grad=("bool", False),
                         smooth_alpha=("float", 0.0)))
def _softmax_output(data, label, grad_scale=1.0, ignore_label=-1.0,
                    multi_output=False, use_ignore=False, preserve_shape=False,
                    normalization="null", out_grad=False, smooth_alpha=0.0):
    return _softmax_output_core(data, label, grad_scale, ignore_label,
                                multi_output, use_ignore, preserve_shape,
                                normalization, out_grad)


def _make_regression_output(name, fwd, grad):
    @functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
    def core(data, label, grad_scale):
        return fwd(data)

    def core_fwd(data, label, grad_scale):
        out = fwd(data)
        return out, (out, label)

    def core_bwd(grad_scale, res, g):
        out, label = res
        gd = grad(out, label.reshape(out.shape)) * grad_scale
        return (gd, jnp.zeros_like(label))

    core.defvjp(core_fwd, core_bwd)

    @register(name, num_inputs=2, input_names=["data", "label"],
              param_shapes=lambda attrs, shapes: [shapes[0], shapes[0]],
              attrs=AttrSpec(grad_scale=("float", 1.0)))
    def op(data, label, grad_scale=1.0):
        return core(data, label, grad_scale)

    return op


_make_regression_output("LinearRegressionOutput", lambda x: x,
                        lambda o, l: o - l)
_make_regression_output("MAERegressionOutput", lambda x: x,
                        lambda o, l: jnp.sign(o - l))
_make_regression_output("LogisticRegressionOutput", jax.nn.sigmoid,
                        lambda o, l: o - l)


@register("softmax_cross_entropy", num_inputs=2, input_names=["data", "label"],
          param_shapes=lambda attrs, shapes: [shapes[0], (shapes[0][0],)])
def _softmax_cross_entropy(data, label):
    logp = jax.nn.log_softmax(data, axis=-1)
    picked = jnp.take_along_axis(logp, label.astype(jnp.int32)[:, None], axis=-1)
    return -jnp.sum(picked).reshape(1)


def _token_cross_entropy_input_names(attrs):
    return ["data", "label"] + (["weight", "stats"]
                                if attrs.get("weighted") else [])


@register("TokenCrossEntropy", attrs=AttrSpec(weighted=("bool", False)),
          num_inputs=None, input_names=["data", "label", "weight", "stats"],
          param_shapes=lambda attrs, shapes: [shapes[0], shapes[0][:-1]] + (
              [shapes[0][:-1], (1,)] if attrs.get("weighted") else []),
          needs_is_train=True,
          aux_inputs=lambda attrs: (3,) if attrs.get("weighted") else (),
          aux_update={1: 3}, aux_counters={3: ("loss.weighted_tokens",)})
def _token_cross_entropy(data, label, weight=None, stats=None,
                         weighted=False, _is_train=False):
    """Mean cross-entropy of ``data`` (..., V) logits against ``label``
    (...) class ids, shape (1,): a loss head whose output IS the loss, so
    the step hands no (tokens, V) softmax back and a plain ``jax.vjp`` with
    a ones cotangent gives the gradient of the mean. The log-softmax runs
    in float32 whatever the logits' dtype.

    With ``weighted`` a third input ``weight`` (...) float32 gives every
    position its weight: the loss is ``sum(w * (lse - picked))`` over the
    COUNT of positions (not the sum of the weights), so a position of
    weight 0 adds nothing and its logits get a zero gradient. The
    auxiliary state ``stats`` (1,) then counts, on the device and per
    training step, the positions with ``w > 0``
    (``loss.weighted_tokens``): read it at a boundary
    (``SPMDTrainer.aux_counters``), never every step."""
    logits = data.reshape(-1, data.shape[-1]).astype(jnp.float32)
    idx = label.reshape(-1).astype(jnp.int32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, idx[:, None], axis=-1)[:, 0]
    if not weighted:
        return jnp.mean(lse - picked).reshape(1)
    w = weight.reshape(-1).astype(jnp.float32)
    loss = (jnp.sum(w * (lse - picked)) / w.size).reshape(1)
    if _is_train:
        stats = stats + jnp.sum(w > 0).astype(stats.dtype)
    return loss, lax.stop_gradient(stats)


OP_TABLE["TokenCrossEntropy"].dynamic_input_names = \
    _token_cross_entropy_input_names


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _svm_core(data, label, margin, reg_coef, use_linear):
    return data


def _svm_fwd(data, label, margin, reg_coef, use_linear):
    return data, (data, label)


def _svm_bwd(margin, reg_coef, use_linear, res, g):
    data, label = res
    lab = label.astype(jnp.int32)
    onehot = jax.nn.one_hot(lab, data.shape[-1], dtype=data.dtype)
    sign = 2 * onehot - 1  # +1 at true class, -1 elsewhere
    viol = (margin - sign * data) > 0
    if use_linear:
        grad = jnp.where(viol, -sign * reg_coef, 0.0)
    else:
        grad = jnp.where(viol, -2 * (margin - sign * data) * sign * reg_coef, 0.0)
    return (grad.astype(data.dtype), jnp.zeros_like(label))


_svm_core.defvjp(_svm_fwd, _svm_bwd)


@register("SVMOutput", num_inputs=2, input_names=["data", "label"],
          param_shapes=lambda attrs, shapes: [shapes[0], (shapes[0][0],)],
          attrs=AttrSpec(margin=("float", 1.0),
                         regularization_coefficient=("float", 1.0),
                         use_linear=("bool", False)))
def _svm_output(data, label, margin=1.0, regularization_coefficient=1.0,
                use_linear=False):
    return _svm_core(data, label, margin, regularization_coefficient, use_linear)
