"""Sequence / context parallelism: ring attention and Ulysses.

The reference (2017) has no sequence parallelism — its only long-sequence
tools are bucketing (docs/how_to/bucketing.md) and manual ctx_group layer
placement (example/model-parallel-lstm/lstm.py:65-129). These are the
TPU-native replacements called for by SURVEY.md §5.7: shard the *sequence*
axis of attention over a named mesh axis and move KV blocks over ICI.

Two schemes, both SPMD under ``jax.shard_map``:

- **Ring attention** (`ring_attention`): K/V blocks rotate around the mesh
  axis with ``jax.lax.ppermute`` while each device accumulates blockwise
  online-softmax partial attention for its resident Q block. Memory per
  device is O(S/n); comm rides ICI neighbor links and overlaps with the
  per-block matmuls.
- **Ulysses** (`ulysses_attention`): two ``jax.lax.all_to_all`` reshards —
  sequence-sharded -> head-sharded, run *full* local attention, and back.
  Cheaper compute schedule than ring when heads % n == 0.

Both are exact (not approximations): outputs match single-device softmax
attention to float tolerance, verified in tests/test_sequence_parallel.py
on an 8-device CPU mesh.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.lax import axis_size
from jax.sharding import Mesh, PartitionSpec as P

from ..base import MXNetError

__all__ = ["ring_attention", "ulysses_attention", "sequence_sharded_attention"]

_NEG = -1e30


def _check_seq_divides(q, k, mesh: Mesh, axis_name: str):
    if axis_name not in mesh.axis_names:
        raise MXNetError(f"mesh has no axis {axis_name!r}")
    n = mesh.shape[axis_name]
    for name, a in (("q", q), ("k/v", k)):
        if a.shape[2] % n:
            raise MXNetError(
                f"{name} seq length {a.shape[2]} not divisible by mesh "
                f"axis {axis_name!r} size {n}")


def _block(q, k, v, kpos, qpos, scale, causal, carry):
    """One blockwise online-softmax accumulation step.

    q: (B,H,Sq,D); k,v: (B,H,Sk,D); qpos/kpos: global token positions.
    carry = (m, l, o) running max / normalizer / unnormalized output.
    """
    m_prev, l_prev, o_prev = carry
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        mask = kpos[None, None, None, :] <= qpos[None, None, :, None]
        s = jnp.where(mask, s, _NEG)
    m_cur = jnp.max(s, axis=-1)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new[..., None])
    if causal:
        p = jnp.where(mask, p, 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_prev * alpha + jnp.sum(p, axis=-1)
    o_new = o_prev * alpha[..., None] + jnp.einsum(
        "bhqk,bhkd->bhqd", p, v.astype(p.dtype))
    return m_new, l_new, o_new


def _ring_attn_local(q, k, v, axis_name: str, causal: bool,
                     scale: Optional[float]):
    """Per-shard body: rotate K/V blocks around `axis_name`, accumulate."""
    n = axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, h, sq, d = q.shape
    sk = k.shape[2]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    qf = q.astype(jnp.float32)
    qpos = idx * sq + jnp.arange(sq)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(r, acc):
        k_r, v_r, carry = acc
        src = (idx - r) % n  # who this block started on
        kpos = src * sk + jnp.arange(sk)
        if causal and sq == sk:
            # with contiguous equal-length sharding a block from a later
            # device is entirely masked (min kpos > max qpos) — skip its
            # matmuls; unequal q/k shard lengths fall through to the
            # position mask below, which is always correct
            carry = jax.lax.cond(
                src <= idx,
                lambda c: _block(qf, k_r.astype(jnp.float32), v_r, kpos,
                                 qpos, scale, True, c),
                lambda c: c, carry)
        else:
            carry = _block(qf, k_r.astype(jnp.float32), v_r, kpos, qpos,
                           scale, causal, carry)
        # rotate for the next step (the final rotate is dead but keeps the
        # loop body uniform; XLA overlaps it with the block compute)
        k_r = jax.lax.ppermute(k_r, axis_name, perm)
        v_r = jax.lax.ppermute(v_r, axis_name, perm)
        return k_r, v_r, carry

    init = (jnp.full((b, h, sq), _NEG, jnp.float32),
            jnp.zeros((b, h, sq), jnp.float32),
            jnp.zeros((b, h, sq, d), jnp.float32))
    _, _, (m, l, o) = jax.lax.fori_loop(0, n, step, (k, v, init))
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


def _bh_axes(q, mesh: Mesh, seq_axis: str, batch_axis: Optional[str],
             head_axis: Optional[str]):
    """Batch/head partition entries for the shard_map specs, so sequence
    parallelism composes with dp (batch over ``data``) and tp (heads over
    ``model``) in one 3-D/4-D mesh."""
    b_ax = (batch_axis if batch_axis and batch_axis != seq_axis
            and batch_axis in mesh.axis_names
            and q.shape[0] % mesh.shape[batch_axis] == 0 else None)
    h_ax = (head_axis if head_axis and head_axis != seq_axis
            and head_axis in mesh.axis_names
            and q.shape[1] % mesh.shape[head_axis] == 0 else None)
    return b_ax, h_ax


def ring_attention(q, k, v, mesh: Mesh, axis_name: str = "seq",
                   causal: bool = False, scale: Optional[float] = None,
                   batch_axis: Optional[str] = "data",
                   head_axis: Optional[str] = "model"):
    """Exact attention with the sequence axis sharded over ``axis_name``.

    Inputs are (batch, heads, seq, head_dim), logically full-length; the
    wrapper shards seq over the mesh axis, each device keeps its Q block
    resident and K/V blocks rotate around the ring via ppermute. When the
    mesh also has ``batch_axis``/``head_axis`` axes, batch and heads are
    partitioned over them (dp x tp x sp composition).
    """
    _check_seq_divides(q, k, mesh, axis_name)
    b_ax, h_ax = _bh_axes(q, mesh, axis_name, batch_axis, head_axis)
    spec = P(b_ax, h_ax, axis_name, None)
    fn = shard_map(
        functools.partial(_ring_attn_local, axis_name=axis_name,
                          causal=causal, scale=scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    return fn(q, k, v)


def _full_attn(q, k, v, causal, scale):
    d = q.shape[-1]
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        sq, sk = s.shape[-2:]
        mask = jnp.arange(sk)[None, :] <= jnp.arange(sq)[:, None]
        s = jnp.where(mask, s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


def _ulysses_local(q, k, v, axis_name: str, causal: bool,
                   scale: Optional[float]):
    """seq-sharded -> all_to_all -> head-sharded full attention -> back."""
    a2a = functools.partial(jax.lax.all_to_all, axis_name=axis_name,
                            split_axis=1, concat_axis=2, tiled=True)
    qh, kh, vh = a2a(q), a2a(k), a2a(v)  # (B, H/n, S, D)
    oh = _full_attn(qh, kh, vh, causal, scale)
    return jax.lax.all_to_all(oh, axis_name=axis_name, split_axis=2,
                              concat_axis=1, tiled=True)


def ulysses_attention(q, k, v, mesh: Mesh, axis_name: str = "seq",
                      causal: bool = False, scale: Optional[float] = None,
                      batch_axis: Optional[str] = "data",
                      head_axis: Optional[str] = "model"):
    """Exact attention via head<->sequence all_to_all reshard (Ulysses).

    Requires (per-``head_axis``-shard) heads % mesh.shape[axis_name] == 0.
    Inputs (B, H, S, D). Batch/heads partition over ``batch_axis``/
    ``head_axis`` when those mesh axes exist (dp x tp x sp composition).
    """
    _check_seq_divides(q, k, mesh, axis_name)
    n = mesh.shape[axis_name]
    b_ax, h_ax = _bh_axes(q, mesh, axis_name, batch_axis, head_axis)
    local_heads = q.shape[1] // (mesh.shape[h_ax] if h_ax else 1)
    if local_heads % n:
        raise MXNetError(
            f"ulysses needs local heads ({local_heads}) divisible by mesh "
            f"axis {axis_name!r} ({n})")
    spec = P(b_ax, h_ax, axis_name, None)
    fn = shard_map(
        functools.partial(_ulysses_local, axis_name=axis_name,
                          causal=causal, scale=scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    return fn(q, k, v)


def sequence_sharded_attention(q, k, v, mesh: Mesh, axis_name: str = "seq",
                               causal: bool = False,
                               scale: Optional[float] = None,
                               mode: str = "auto",
                               batch_axis: Optional[str] = "data",
                               head_axis: Optional[str] = "model"):
    """Dispatch: 'ring', 'ulysses', or 'auto' (ulysses when heads divide)."""
    if axis_name not in mesh.axis_names:
        raise MXNetError(f"mesh has no axis {axis_name!r}")
    if mode == "auto":
        n = mesh.shape[axis_name]
        _, h_ax = _bh_axes(q, mesh, axis_name, batch_axis, head_axis)
        local_heads = q.shape[1] // (mesh.shape[h_ax] if h_ax else 1)
        mode = "ulysses" if local_heads % n == 0 else "ring"
    if mode == "ring":
        return ring_attention(q, k, v, mesh, axis_name, causal, scale,
                              batch_axis, head_axis)
    if mode == "ulysses":
        return ulysses_attention(q, k, v, mesh, axis_name, causal, scale,
                                 batch_axis, head_axis)
    raise MXNetError(f"unknown sequence-parallel mode {mode!r}")
