"""Attention operators: mesh-aware multi-head attention for sym/nd/gluon.

Beyond-reference (the 2017 reference has no attention op; its long-sequence
tools are bucketing + ctx_group placement, SURVEY.md §5.7). This op makes
the TPU-native sequence-parallel kernels (`parallel/sequence.py` ring /
Ulysses attention) reachable from the *user-facing graph languages*: a
Symbol/NDArray op whose ``seq_axis`` attr names a mesh axis. When an
ambient mesh (``parallel.mesh_scope`` — entered automatically by
SPMDTrainer) carries that axis, attention runs sequence-parallel over it,
composing with ``data`` (batch) and ``model`` (heads) axes; otherwise it
falls back to ordinary full softmax attention, so the same graph runs
anywhere from one chip to a 4-D mesh.
"""
from __future__ import annotations

import contextlib
import functools
import math

import jax
import jax.numpy as jnp

from ..base import AttrSpec, MXNetError
from .pallas import rotary
from .registry import register


def _split_heads(x, num_heads):
    b, s, e = x.shape
    if e % num_heads:
        raise MXNetError(
            f"MultiHeadAttention: embed dim {e} not divisible by "
            f"num_heads {num_heads}")
    return x.reshape(b, s, num_heads, e // num_heads).transpose(0, 2, 1, 3)


def _merge_heads(x):
    b, h, s, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, s, h * d)


@register("MultiHeadAttention",
          attrs=AttrSpec(num_heads=("int",), causal=("bool", False),
                         seq_axis=("str", ""), seq_mode=("str", "auto"),
                         batch_axis=("str", "data"),
                         head_axis=("str", "model")),
          num_inputs=3, input_names=["query", "key", "value"],
          output_names=["output"])
def _multi_head_attention(query, key, value, num_heads, causal=False,
                          seq_axis="", seq_mode="auto", batch_axis="data",
                          head_axis="model"):
    """Scaled-dot-product multi-head attention over (B, S, E) inputs.

    ``seq_axis``: name of a mesh axis to shard the sequence over. Looked
    up on the ambient :func:`parallel.current_mesh` at trace time; absent
    mesh/axis (or axis size 1) falls back to full local attention with
    identical numerics. ``seq_mode``: 'ring' (ppermute KV rotation),
    'ulysses' (head<->seq all_to_all), or 'auto'.
    """
    q = _split_heads(query, num_heads)
    k = _split_heads(key, num_heads)
    v = _split_heads(value, num_heads)
    mesh = None
    if seq_axis:
        from ..parallel.mesh import current_mesh
        m = current_mesh()
        if (m is not None and seq_axis in m.axis_names
                and m.shape[seq_axis] > 1
                and q.shape[2] % m.shape[seq_axis] == 0
                and k.shape[2] % m.shape[seq_axis] == 0):
            mesh = m
    if mesh is not None:
        from ..parallel.sequence import sequence_sharded_attention
        out = sequence_sharded_attention(
            q, k, v, mesh, axis_name=seq_axis, causal=causal,
            mode=seq_mode, batch_axis=batch_axis or None,
            head_axis=head_axis or None)
    else:
        from ..parallel.sequence import _full_attn
        out = _full_attn(q, k, v, causal, None)
    return _merge_heads(out).astype(query.dtype)


# -- the decoder's attention: rotary positions, grouped query heads ----------

def rotary_frequencies(rotary_dim, theta, rope_type="default", factor=1.0,
                       original_max_position=0, beta_fast=32.0,
                       beta_slow=1.0):
    """The rotary_dim / 2 inverse frequencies. ``default``:
    theta^(-2i/r). ``yarn`` (Peng et al. 2023): frequencies whose wavelength
    fits the original context many times keep their value, those that do
    not are divided by ``factor``, with a linear ramp between the two over
    the dimensions that turn ``beta_fast`` to ``beta_slow`` times in
    ``original_max_position`` positions."""
    half = rotary_dim // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / rotary_dim)
    if rope_type == "default":
        return inv
    if rope_type != "yarn":
        raise MXNetError(f"RotaryEmbedding: unknown rope_type {rope_type!r}")

    def turns_dim(turns):
        return rotary_dim * math.log(
            original_max_position / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(turns_dim(beta_fast)), 0)
    high = min(math.ceil(turns_dim(beta_slow)), rotary_dim - 1)
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return inv / factor * ramp + inv * (1.0 - ramp)


def _angles(positions, r, frequencies, attention_factor):
    """cos and sin (positions, r / 2) of position times frequency, times
    ``attention_factor``, float32."""
    angle = jnp.arange(positions, dtype=jnp.float32)[:, None] \
        * rotary_frequencies(r, *frequencies)[None, :]
    return jnp.cos(angle) * attention_factor, jnp.sin(angle) * attention_factor


def _kernel_rotated(data, attrs, negative):
    head_dim, angles, plan = attrs
    return rotary.rotate_pallas(data, *_angles(*angles), head_dim, negative,
                                plan)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _kernel_rotation(data, attrs):
    return _kernel_rotated(data, attrs, False)


# a rotation's transpose is the rotation by the negative angle (the
# attention factor multiplies both ways): the same kernel, nothing kept
_kernel_rotation.defvjp(
    lambda data, attrs: (_kernel_rotated(data, attrs, False), None),
    lambda attrs, _, g: (_kernel_rotated(g, attrs, True),))


@register("RotaryEmbedding",
          attrs=AttrSpec(head_dim=("int",), rotary_dim=("int", 0),
                         theta=("float", 10000.0),
                         rope_type=("str", "default"),
                         factor=("float", 1.0),
                         original_max_position=("int", 0),
                         beta_fast=("float", 32.0),
                         beta_slow=("float", 1.0),
                         attention_factor=("float", 1.0),
                         copies=("int", 1)),
          num_inputs=1, input_names=["data"])
def _rotary_embedding(data, head_dim, rotary_dim=0, theta=10000.0,
                      rope_type="default", factor=1.0,
                      original_max_position=0, beta_fast=32.0,
                      beta_slow=1.0, attention_factor=1.0, copies=1):
    """Rotary position embedding over (B, S, heads * head_dim): position s
    of every head has its first ``rotary_dim`` dims (all of them when 0)
    rotated by s times the frequencies, dimension i paired with
    i + rotary_dim / 2; the rest pass. cos and sin are multiplied by
    ``attention_factor`` (YaRN's scaling of the logits, applied where the
    published models apply it). With ``copies`` > 1 the sequence is that
    many copies of one document laid end to end, each at positions 0 ..
    S / copies - 1: the position of index s is s mod (S / copies). Angles,
    products and the sum in float32, one rounding to the input's dtype.

    On a TPU it runs ``ops/pallas/rotary.py``'s kernel over the tensor laid
    out by head, (B heads, S, head_dim), as the attention kernel reads it and
    as a projection writes it for nothing (the two transpositions are
    logical and cancel against the neighbours'): a row of lanes is one head
    at one position and the exchange of its halves a lane rotation, one read
    and one write of the tensor, where the four-dimensional view over
    (B, S, heads, head_dim) costs a float32 relayout each way. A head size
    that is no multiple of 128 lanes, or a document length that no row tile
    of 8 (16 for a two-byte dtype) divides, keeps the ``jnp`` formulation, as
    does every other backend; the choice reads the input's shape and nothing
    else. Through the kernel the op is one ``jax.custom_vjp`` with no
    residual: its gradient is the rotation by the negative angle, the same
    kernel; the ``jnp`` formulation is differentiated by JAX."""
    b, s, e = data.shape
    r = rotary_dim or head_dim
    if e % head_dim or r > head_dim or r % 2:
        raise MXNetError(
            f"RotaryEmbedding: width {e}, head_dim {head_dim}, rotary_dim "
            f"{r}: the width is a whole number of heads and the rotated "
            f"part an even number of dims inside a head")
    if copies < 1 or s % copies:
        raise MXNetError(
            f"RotaryEmbedding: a sequence of {s} is not {copies} copies of "
            f"one document")
    angles = (s // copies, r, (theta, rope_type, factor,
                               original_max_position, beta_fast, beta_slow),
              attention_factor)
    plan = rotary.kernel_plan(data, s // copies, head_dim)
    if plan is None:
        return rotary.rotate_reference(data, *_angles(*angles), head_dim,
                                       copies)
    return _kernel_rotation(data, (head_dim, angles, plan))


@register("GroupedQueryAttention",
          attrs=AttrSpec(num_heads=("int",), num_kv_heads=("int",),
                         window=("int", 0), causal=("bool", True),
                         gated=("bool", False), block_length=("int", 0)),
          num_inputs=None, input_names=["query", "key", "value", "gate"],
          output_names=["output"])
def _grouped_query_attention(*args, num_heads, num_kv_heads, window=0,
                             causal=True, gated=False, block_length=0):
    """Attention of ``num_heads`` query heads over ``num_kv_heads`` key/value
    heads: query (B, S, num_heads * d), key and value (B, S, num_kv_heads *
    d); query head h reads key/value head h // (num_heads / num_kv_heads).
    Scores q k^T / sqrt(d); ``causal``; ``window`` > 0 lets key j be seen
    from i only if i - window < j <= i. With ``gated`` a fourth input ``gate``
    (B, S, num_heads) multiplies head h's output by sigmoid(gate_h).
    ``block_length`` > 0 (causal, no window) is the block-diffusion
    training mask: the S positions are a noisy copy of a document and then
    the clean one, S / 2 each, in blocks of ``block_length``; with blk(i) =
    (i mod S/2) // block_length and noisy(i) = i < S/2, key k is seen from
    query q where both are noisy and blk(q) == blk(k), or q is noisy, k
    clean and blk(q) > blk(k), or both are clean and blk(q) >= blk(k).
    On a TPU it runs ``ops/pallas/attention.py``'s flash kernel over the
    mask's live tiles (a band, or the block-diffusion walk, under the named
    scope ``block_diffusion``); elsewhere plain masked softmax."""
    from .pallas.attention import grouped_query_attention
    query, key, value = args[:3]
    b, s, e = query.shape
    if e % num_heads or key.shape[-1] % num_kv_heads \
            or e // num_heads != key.shape[-1] // num_kv_heads:
        raise MXNetError(
            f"GroupedQueryAttention: query width {e} over {num_heads} "
            f"heads against key width {key.shape[-1]} over {num_kv_heads}")
    d = e // num_heads

    def heads(x, n):
        return x.reshape(b, s, n, d).transpose(0, 2, 1, 3)

    scope = jax.named_scope("block_diffusion") if block_length \
        else contextlib.nullcontext()
    try:
        with scope:
            out = grouped_query_attention(
                heads(query, num_heads), heads(key, num_kv_heads),
                heads(value, num_kv_heads), causal=causal, window=window,
                block_length=block_length)
    except ValueError as e:
        raise MXNetError(f"GroupedQueryAttention: {e}") from None
    out = out.transpose(0, 2, 1, 3)                      # (B, S, H, d)
    if gated:
        gate = jax.nn.sigmoid(args[3].astype(jnp.float32))
        out = (out.astype(jnp.float32) * gate[..., None]).astype(out.dtype)
    return out.reshape(b, s, e).astype(query.dtype)
