"""Flash attention: VMEM-blocked online-softmax attention kernel.

The jnp path (and the reference's Softmax-based attention compositions)
materialize the (S, S) score matrix in HBM; this kernel streams K/V blocks
through VMEM with the standard online-softmax recurrence, so HBM traffic is
O(S·D) and the MXU sees back-to-back (BQ, D)x(D, BK) matmuls. Public
pattern: Dao et al. 2022 + the Pallas guide's blocked-matmul recipe.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..registry import keep_residual, register
from ... import profiler
from ...base import AttrSpec

_NEG = -1e30


def _attn_reference(q, k, v, causal, scale):
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        sq, sk = s.shape[-2:]
        mask = (jnp.arange(sk)[None, :] <= jnp.arange(sq)[:, None]
                + (sk - sq))
        s = jnp.where(mask, s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  causal, scale, seq_k, seq_q):
    """Grid (BH, n_q, n_k), n_k innermost+sequential. Blocks live in VMEM:
    q (1, BQ, D), k/v (1, BK, D) — only one K/V tile resident at a time, so
    VMEM use is O(BQ*D + BK*D) regardless of S. m/l/acc scratch carries the
    online-softmax state across the n_k loop."""
    ki = pl.program_id(2)
    n_k = pl.num_programs(2)
    bq = q_ref.shape[1]
    bk = k_ref.shape[1]
    qi = pl.program_id(1)
    q_off = qi * bq + (seq_k - seq_q)  # causal diagonal offset

    @pl.when(ki == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # a K block strictly above the causal diagonal contributes nothing
    live = (ki * bk <= q_off + bq - 1) if causal else True

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale
        k_blk = k_ref[0].astype(jnp.float32)
        v_blk = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # (BQ, BK)
        if causal:
            rows = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            cols = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            mask = (ki * bk + cols) <= (q_off + rows)
            s = jnp.where(mask, s, _NEG)
        m_prev = m_ref[:, 0]
        l_prev = l_ref[:, 0]
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[:, None])
        if causal:
            p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1)
        acc_ref[:] = acc_ref[:] * alpha[:, None] + jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = m_new[:, None] + jnp.zeros_like(m_ref)
        l_ref[:] = l_new[:, None] + jnp.zeros_like(l_ref)

    @pl.when(ki == n_k - 1)
    def _finish():
        l = jnp.maximum(l_ref[:, 0], 1e-30)
        o_ref[0] = (acc_ref[:] / l[:, None]).astype(o_ref.dtype)


def _pick_block(s, target):
    b = min(target, s)
    while s % b:
        b //= 2
    return max(b, 1)


def _flash_dense_pallas(q, k, v, causal, scale, block_q, block_k,
                        interpret):
    """Build the dense flash ``pallas_call`` for (B, H, S, D) inputs."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bq = _pick_block(sq, block_q)
    bk = _pick_block(sk, block_k)
    qr = q.reshape(b * h, sq, d)
    kr = k.reshape(b * h, sk, d)
    vr = v.reshape(b * h, sk, d)
    kernel = functools.partial(_flash_kernel, causal=causal, scale=scale,
                               seq_k=sk, seq_q=sq)
    out = pl.pallas_call(
        kernel,
        grid=(b * h, sq // bq, sk // bk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, i, j: (bh, j, 0)),
            pl.BlockSpec((1, bk, d), lambda bh, i, j: (bh, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, i, j: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),  # running max m
            pltpu.VMEM((bq, 128), jnp.float32),  # running normalizer l
            pltpu.VMEM((bq, d), jnp.float32),    # unnormalized output
        ],
        interpret=interpret,
        name="flash_attention",
    )(qr, kr, vr)
    return out.reshape(b, h, sq, d)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
@functools.partial(jax.jit, static_argnames=("causal", "scale", "block_q",
                                             "block_k", "force_pallas"))
def _flash_attention_dense(q, k, v, causal=False, scale=None, block_q=256,
                           block_k=512, force_pallas=False):
    """The dense core: every token is real. Kept custom_vjp'd and
    bitwise-identical to the pre-ragged ``flash_attention`` — the public
    dispatcher routes here whenever no lengths/segment_ids are given."""
    sq, d = q.shape[2:]
    sk = k.shape[2]
    if causal and sq > sk:
        # rows past the KV length would have an empty causal window —
        # an ill-defined softmax the paths disagree on; reject loudly
        raise ValueError(
            f"flash_attention(causal=True) requires seq_q <= seq_k, got "
            f"{sq} > {sk}")
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    on_tpu = jax.default_backend() == "tpu"
    if not on_tpu and not force_pallas:
        return _attn_reference(q, k, v, causal, scale)
    return _flash_dense_pallas(q, k, v, causal, scale, block_q, block_k,
                               interpret=not on_tpu)


def _fa_fwd(q, k, v, causal, scale, block_q, block_k, force_pallas):
    out = _flash_attention_dense(q, k, v, causal, scale, block_q, block_k,
                                 force_pallas)
    return out, (q, k, v, out)


def _blockwise_bwd(q, k, v, out, do, causal, scale, block_k):
    """Flash-attention backward as a k-block scan: O(S*BK) temporaries
    instead of the S x S score matrix (standard Dao et al. recurrence).

    All (B, H, S, D). Two passes: (1) recompute row logsumexp; (2)
    accumulate dq and per-block dk/dv with normalized probabilities.
    """
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bk = _pick_block(sk, block_k)
    n_k = sk // bk
    qf = q.astype(jnp.float32) * scale
    dof = do.astype(jnp.float32)
    # delta_i = sum_j dO_ij O_ij  (rowwise) — the softmax-jacobian constant
    delta = jnp.sum(dof * out.astype(jnp.float32), axis=-1)  # (B,H,S)
    qpos = jnp.arange(sq)
    kb = k.reshape(b, h, n_k, bk, d).transpose(2, 0, 1, 3, 4)
    vb = v.reshape(b, h, n_k, bk, d).transpose(2, 0, 1, 3, 4)

    def scores(k_blk, j):
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, k_blk.astype(jnp.float32))
        if causal:
            # same diagonal convention as the forward kernel:
            # kpos <= qpos + (sk - sq)
            kpos = j * bk + jnp.arange(bk)
            mask = (kpos[None, None, None, :]
                    <= qpos[None, None, :, None] + (sk - sq))
            s = jnp.where(mask, s, _NEG)
        return s

    # pass 1: logsumexp over all key blocks
    def lse_step(carry, inp):
        m, l = carry
        j, k_blk = inp
        s = scores(k_blk, j)
        m_cur = jnp.max(s, -1)
        m_new = jnp.maximum(m, m_cur)
        l = l * jnp.exp(m - m_new) + jnp.sum(jnp.exp(s - m_new[..., None]),
                                             -1)
        return (m_new, l), None

    (m, l), _ = jax.lax.scan(
        lse_step,
        (jnp.full((b, h, sq), _NEG, jnp.float32),
         jnp.zeros((b, h, sq), jnp.float32)),
        (jnp.arange(n_k), kb))
    lse = m + jnp.log(jnp.maximum(l, 1e-30))

    # pass 2: gradient accumulation
    def grad_step(dq, inp):
        j, k_blk, v_blk = inp
        s = scores(k_blk, j)
        p = jnp.exp(s - lse[..., None])  # normalized probs (B,H,S,BK)
        dv_b = jnp.einsum("bhqk,bhqd->bhkd", p, dof)
        dp = jnp.einsum("bhqd,bhkd->bhqk", dof,
                        v_blk.astype(jnp.float32))
        ds = p * (dp - delta[..., None]) * scale
        dq = dq + jnp.einsum("bhqk,bhkd->bhqd", ds,
                             k_blk.astype(jnp.float32))
        # ds folds the score scale; dk pairs with the UNscaled q
        dk_b = jnp.einsum("bhqk,bhqd->bhkd", ds, q.astype(jnp.float32))
        return dq, (dk_b, dv_b)

    dq, (dk_blocks, dv_blocks) = jax.lax.scan(
        grad_step, jnp.zeros((b, h, sq, d), jnp.float32),
        (jnp.arange(n_k), kb, vb))
    dk = dk_blocks.transpose(1, 2, 0, 3, 4).reshape(b, h, sk, d)
    dv = dv_blocks.transpose(1, 2, 0, 3, 4).reshape(b, h, sk, d)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def _fa_bwd(causal, scale, block_q, block_k, force_pallas, res, ct):
    q, k, v, out = res
    s = scale if scale is not None else 1.0 / (q.shape[-1] ** 0.5)
    return _blockwise_bwd(q, k, v, out, ct, causal, s, block_k)


_flash_attention_dense.defvjp(_fa_fwd, _fa_bwd)


# -- length/segment-masked attention (the ragged serving rung) ---------------

def _combined_mask(sq, sk, causal, lengths, segment_ids):
    """(B, 1, SQ, SK) bool mask — True = attend. Folds the causal
    diagonal, per-batch KEY lengths (kpos < length), and packed-row
    segment ids (same NONZERO segment attends; 0 marks pad tokens,
    which attend to and from nothing)."""
    mask = None
    if causal:
        mask = (jnp.arange(sk)[None, :]
                <= jnp.arange(sq)[:, None] + (sk - sq))[None, None]
    if lengths is not None:
        lmask = (jnp.arange(sk)[None, :]
                 < lengths.astype(jnp.int32)[:, None])[:, None, None, :]
        mask = lmask if mask is None else mask & lmask
    if segment_ids is not None:
        seg = segment_ids.astype(jnp.int32)
        smask = ((seg[:, None, :, None] == seg[:, None, None, :])
                 & (seg[:, None, :, None] > 0))
        mask = smask if mask is None else mask & smask
    return mask


def _masked_reference(q, k, v, lengths, segment_ids, causal, scale):
    """jnp path of the masked core. Fully-masked query rows (pad
    tokens, positions past their sequence's length) output exact 0 —
    the same convention the Pallas masked kernel lands on, so the two
    paths stay allclose row-for-row including pad rows."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    mask = _combined_mask(s.shape[-2], s.shape[-1], causal,
                          lengths, segment_ids)
    s = jnp.where(mask, s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(mask, p, 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


def _flash_kernel_masked(*refs, causal, scale, seq_k, seq_q,
                         has_len, has_seg):
    """The masked variant of :func:`_flash_kernel`: same grid, same
    online-softmax recurrence, with the in-block mask extended by the
    per-batch key length and/or the packed segment ids (pallas guide:
    ``broadcasted_iota`` + ``jnp.where``; TPU needs the >=2D iota).
    The lengths arrive by scalar prefetch — (B*H,) int32 in SMEM — and
    the segment ids as a (bq, 1) column and a (1, bk) row, the layouts
    the TPU lowering takes."""
    it = iter(refs)
    len_ref = next(it) if has_len else None
    q_ref, k_ref, v_ref = next(it), next(it), next(it)
    segq_ref = next(it) if has_seg else None
    segk_ref = next(it) if has_seg else None
    o_ref, m_ref, l_ref, acc_ref = next(it), next(it), next(it), next(it)
    kv_len = len_ref[pl.program_id(0)] if has_len else None
    ki = pl.program_id(2)
    n_k = pl.num_programs(2)
    bq = q_ref.shape[1]
    bk = k_ref.shape[1]
    qi = pl.program_id(1)
    q_off = qi * bq + (seq_k - seq_q)

    @pl.when(ki == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    live = (ki * bk <= q_off + bq - 1) if causal else True

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale
        k_blk = k_ref[0].astype(jnp.float32)
        v_blk = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # (BQ, BK)
        rows = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= (ki * bk + cols) <= (q_off + rows)
        if has_len:
            mask &= (ki * bk + cols) < kv_len
        if has_seg:
            seg_q = segq_ref[0]         # (bq, 1)
            seg_k = segk_ref[0]         # (1, bk)
            mask &= (seg_q == seg_k) & (seg_q > 0)
        s = jnp.where(mask, s, _NEG)
        m_prev = m_ref[:, 0]
        l_prev = l_ref[:, 0]
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[:, None])
        # explicit zeroing, not just the _NEG shift: an ALL-masked first
        # block has s == m_new, where exp would give 1.0 per position
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1)
        acc_ref[:] = acc_ref[:] * alpha[:, None] + jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = m_new[:, None] + jnp.zeros_like(m_ref)
        l_ref[:] = l_new[:, None] + jnp.zeros_like(l_ref)

    @pl.when(ki == n_k - 1)
    def _finish():
        l = jnp.maximum(l_ref[:, 0], 1e-30)
        o_ref[0] = (acc_ref[:] / l[:, None]).astype(o_ref.dtype)


def _masked_pallas(q, k, v, lengths, segment_ids, causal, scale, block_q,
                   block_k, interpret):
    """Build the masked flash ``pallas_call`` for (B, H, S, D) inputs."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bq = _pick_block(sq, block_q)
    bk = _pick_block(sk, block_k)
    # index maps see the grid indices, then any scalar-prefetch refs
    operands = [q.reshape(b * h, sq, d), k.reshape(b * h, sk, d),
                v.reshape(b * h, sk, d)]
    in_specs = [
        pl.BlockSpec((1, bq, d), lambda bh, i, j, *_: (bh, i, 0)),
        pl.BlockSpec((1, bk, d), lambda bh, i, j, *_: (bh, j, 0)),
        pl.BlockSpec((1, bk, d), lambda bh, i, j, *_: (bh, j, 0)),
    ]
    prefetch = []
    if lengths is not None:
        # one key length per batch element, broadcast over heads
        prefetch.append(jnp.broadcast_to(
            lengths.astype(jnp.int32)[:, None], (b, h)).reshape(b * h))
    if segment_ids is not None:
        seg = segment_ids.astype(jnp.int32)
        operands.extend([seg[:, :, None], seg[:, None, :]])
        in_specs.extend([
            pl.BlockSpec((1, bq, 1), lambda bh, i, j, *_: (bh // h, i, 0)),
            pl.BlockSpec((1, 1, bk), lambda bh, i, j, *_: (bh // h, 0, j)),
        ])
    kernel = functools.partial(
        _flash_kernel_masked, causal=causal, scale=scale, seq_k=sk,
        seq_q=sq, has_len=lengths is not None,
        has_seg=segment_ids is not None)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(b * h, sq // bq, sk // bk),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, bq, d),
                                   lambda bh, i, j, *_: (bh, i, 0)),
            scratch_shapes=[
                pltpu.VMEM((bq, 128), jnp.float32),  # running max m
                pltpu.VMEM((bq, 128), jnp.float32),  # running normalizer l
                pltpu.VMEM((bq, d), jnp.float32),    # unnormalized output
            ]),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        interpret=interpret,
        name="flash_attention_masked",
    )(*prefetch, *operands)
    return out.reshape(b, h, sq, d)


@functools.partial(jax.jit, static_argnames=("causal", "scale", "block_q",
                                             "block_k", "force_pallas"))
def _masked_attention(q, k, v, lengths, segment_ids, causal=False,
                      scale=None, block_q=256, block_k=512,
                      force_pallas=False):
    """The masked core: plain jit (differentiable through the jnp
    reference path), Pallas masked kernel on TPU/force_pallas."""
    sq, d = q.shape[2:]
    sk = k.shape[2]
    if causal and sq > sk:
        raise ValueError(
            f"flash_attention(causal=True) requires seq_q <= seq_k, got "
            f"{sq} > {sk}")
    if segment_ids is not None and sq != sk:
        raise ValueError(
            f"segment_ids masking is self-attention only (seq_q == "
            f"seq_k); got {sq} != {sk}")
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    on_tpu = jax.default_backend() == "tpu"
    if not on_tpu and not force_pallas:
        return _masked_reference(q, k, v, lengths, segment_ids,
                                 causal, scale)
    return _masked_pallas(q, k, v, lengths, segment_ids, causal, scale,
                          block_q, block_k, interpret=not on_tpu)


def flash_attention(q, k, v, causal=False, scale=None, block_q=256,
                    block_k=512, force_pallas=False, lengths=None,
                    segment_ids=None):
    """Attention over (B, H, S, D) inputs; exact, memory-efficient.

    On a TPU backend this is always the compiled Pallas kernel. On any
    other backend it is the jnp reference, or with ``force_pallas`` the
    kernel through the Pallas interpreter (tests).

    ``lengths`` (B,) int — per-batch real KEY length; positions at or
    past it are masked out. ``segment_ids`` (B, S) int — packed-row
    bookkeeping (serving/ragged.py): tokens attend only within their
    own nonzero segment, 0 marks pad tokens (masked entirely; their
    output rows are exact 0). With neither given, the call routes to
    the unchanged dense ``custom_vjp`` core — bitwise-identical to the
    pre-ragged behavior, gradients included."""
    if lengths is None and segment_ids is None:
        return _flash_attention_dense(q, k, v, causal, scale, block_q,
                                      block_k, force_pallas)
    return _masked_attention(q, k, v, lengths, segment_ids, causal,
                             scale, block_q, block_k, force_pallas)


# -- grouped query heads, causal window (the decoder's attention) -------------

_OPENS, _CLOSES, _CUT = 1, 2, 4     # a visit's flags


def _host_ints(values):
    """A list of Python numbers as a read-only int32 array (the tables are
    cached, and every caller gets the one copy). Nothing traced comes to the
    host here, whatever traced function asks."""
    x = np.asarray(values, np.int32)  # tpu-lint: disable=host-sync-under-trace
    x.setflags(write=False)
    return x


@functools.lru_cache(maxsize=None)
def _visit_table(n_q, n_k, bq, bk, causal, window, block_length=0):
    """The schedule of the kernel and of its backward, built on the host from
    the static shapes: ``(q_tile, k_tile, flags)``, three int32 arrays with
    one entry a LIVE tile of the mask (a tile with a pair that sees), query
    tile by query tile and within one by rising key tile, so a query tile's
    visits are one run. ``flags`` says whether the visit opens its query
    tile's walk (``_OPENS``), closes it (``_CLOSES``), and whether the mask
    cuts the tile (``_CUT``: it holds a dead pair too; a tile that is not cut
    needs no mask).

    One rule serves the three masks: inside a tile, seeing depends on the
    difference of two whole numbers only, each running over the tile's rows
    or keys. Under a band they are the positions, and ``q - k`` has to lie in
    [0, window - 1], [0, far) under plain causality, anywhere without it.
    Under the block-diffusion mask over ``[noisy ; clean]`` halves
    (:func:`_band_mask`; a tile lies in one half, a block in one tile) they
    are the block numbers within the half, and ``blk(q) - blk(k)`` has to be 0
    from noisy to noisy, at least 1 from noisy to clean, at least 0 from clean
    to clean; clean sees nothing noisy. A tile's pairs take every difference
    from ``least`` to ``most``: the tile is live where that run meets the
    allowed one, and cut where it also leaves it."""
    far = n_q * bq + n_k * bk           # past every difference there is
    q_tile, k_tile, flags = [], [], []
    for i in range(n_q):
        opened = len(flags)
        for j in range(n_k):
            q0, k0, unit, lo, hi = i * bq, j * bk, 1, -far, far
            if block_length:
                q_noisy, k_noisy = i < n_q // 2, j < n_k // 2
                if k_noisy and not q_noisy:
                    continue
                q0, k0 = i % (n_q // 2) * bq, j % (n_k // 2) * bk
                unit = block_length
                lo, hi = (0, 0) if k_noisy else (1 if q_noisy else 0, far)
            elif causal:
                lo, hi = 0, window - 1 if window else far
            least = q0 // unit - (k0 + bk - 1) // unit
            most = (q0 + bq - 1) // unit - k0 // unit
            if most < lo or least > hi:
                continue
            q_tile.append(i)
            k_tile.append(j)
            flags.append(_CUT if least < lo or most > hi else 0)
        if len(flags) == opened:
            raise ValueError(f"query tile {i} of {n_q} sees no key")
        flags[opened] |= _OPENS
        flags[-1] |= _CLOSES
    return _host_ints(q_tile), _host_ints(k_tile), _host_ints(flags)


def _run(i, blk, n, causal, window, block_length=0, xp=jnp):
    """``(lo, hi, key_of)``: query tile ``i``'s run of :func:`_visit_table`
    in closed form, for square tiles of ``blk`` over ``n`` of them: its
    visits are the key tiles ``key_of(t)`` for t in [lo, hi). ``i`` may be
    traced (``xp`` = jnp) or a number (``xp`` = np: how
    :func:`_checked_run` holds every tile's run against the table). The
    backward's loops take their bounds and key tiles from here and not from
    the table's arrays, because XLA compiles arithmetic on the loop counters
    into a loop that adds each key tile's dk and dv in place, where bounds
    read from an array cost the whole step 20% of the backward's time on
    LFM2 and Laguna (PERF.md, PR 34).

    A band is one run of rising key tiles, the loop variable the key tile
    itself. Under the block-diffusion mask over two halves of n / 2 tiles a
    noisy tile i reads its own tile and then the clean tiles from n / 2 up,
    as far as its own clean tile, or the one before where a block fills the
    tile; clean tile n / 2 + i reads n / 2 .. n / 2 + i."""
    if block_length:
        half = n // 2
        noisy = i < half
        steps = xp.where(noisy, 1 + i + (block_length < blk), i - half + 1)

        def key_of(t):
            return xp.where(noisy, xp.where(t == 0, i, half + t - 1),
                            half + t)
        return 0 * i, steps, key_of
    if not causal:
        return 0 * i, 0 * i + n, lambda t: t
    first = xp.maximum(i * blk - (window - 1), 0) // blk if window else 0 * i
    return first, i + 1, lambda t: t


def _checked_run(blk, n, causal, window, block_length=0):
    """Hold :func:`_run` to the table, tile by tile, on the host (a few
    hundred numbers a trace): the mask has one description, and the closed
    form is a view of it or an error."""
    q_tile, k_tile, _ = _visit_table(n, n, blk, blk, causal, window,
                                     block_length)
    for i in range(n):
        lo, hi, key_of = _run(i, blk, n, causal, window, block_length, np)
        walked = [int(key_of(t)) for t in range(int(lo), int(hi))]
        listed = k_tile[q_tile == i].tolist()
        if walked != listed:
            raise NotImplementedError(
                f"query tile {i} of {n} (tiles of {blk}, window {window}, "
                f"blocks of {block_length}): the backward's closed form "
                f"walks key tiles {walked}, the table {listed}")


def _band_mask(qpos, kpos, causal, window, block_length=0, half=0):
    """True where key ``kpos`` is seen from query ``qpos`` (broadcastable
    int32 arrays), or None where every key is. With ``block_length`` the
    sequence is two halves of ``half`` positions, a noisy copy and then a
    clean one, in blocks of ``block_length``:

        blk(i) = (i mod half) // block_length;  noisy(i) = i < half
        see(q, k) =  noisy(q) and  noisy(k) and blk(q) == blk(k)
                  or noisy(q) and !noisy(k) and blk(q) >  blk(k)
                  or !noisy(q) and !noisy(k) and blk(q) >= blk(k)
    """
    if block_length:
        q_noisy, k_noisy = qpos < half, kpos < half

        def blk(pos, noisy):
            # positions are whole and not negative: the truncating division
            pos = jnp.where(noisy, pos, pos - half)
            return jax.lax.div(pos, jnp.asarray(block_length, pos.dtype))

        q_blk, k_blk = blk(qpos, q_noisy), blk(kpos, k_noisy)
        # the three lines as two comparisons of a query's number with a
        # key's, so that a column of queries against a row of keys does its
        # arithmetic on the column and the row and three operations on the
        # tile. Noisy to noisy: equal tags, a clean query's and a clean
        # key's tags being two numbers no block has. To a clean key: the
        # query's block, less one where it is noisy (> for >=), against the
        # key's, a noisy key's being past every block
        far = jnp.iinfo(k_blk.dtype).max
        same = jnp.where(q_noisy, q_blk, -1) == jnp.where(k_noisy, k_blk, -2)
        before = q_blk - q_noisy.astype(q_blk.dtype) \
            >= jnp.where(k_noisy, far, k_blk)
        return same | before
    if not causal:
        return None
    mask = kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    return mask


def gqa_attention_reference(q, k, v, causal=True, window=0, scale=None,
                            block_length=0):
    """Plain softmax attention with grouped query heads: q (B, H, S, D),
    k/v (B, Hkv, S, D), query head h reads key/value head h // (H/Hkv);
    ``window`` > 0 lets key j be seen from i only if i - window < j <= i;
    ``block_length`` > 0 is the block-diffusion mask over two halves of
    S / 2 (:func:`_band_mask`). Scores and softmax in float32. The CPU
    path, and what the kernel and its backward are tested against."""
    b, h, s, d = q.shape
    hkv = k.shape[1]
    scale = 1.0 / (d ** 0.5) if scale is None else scale
    q5 = q.reshape(b, hkv, h // hkv, s, d)
    sc = jnp.einsum("bkgqd,bkcd->bkgqc", q5, k,
                    preferred_element_type=jnp.float32) * scale
    pos = jnp.arange(s)
    mask = _band_mask(pos[:, None], pos[None, :], causal, window,
                      block_length, s // 2)
    if mask is not None:
        sc = jnp.where(mask, sc, _NEG)
    p = jax.nn.softmax(sc, axis=-1)
    out = jnp.einsum("bkgqc,bkcd->bkgqd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, h, s, d).astype(q.dtype)


def _lanes(x, n):
    """``x`` (rows, 128) with every lane of a row equal, over ``n`` lanes."""
    copies = -(-n // x.shape[1])
    x = jnp.tile(x, (1, copies)) if copies > 1 else x
    return x if x.shape[1] == n else x[:, :n]


def _gqa_kernel(qt_ref, kt_ref, fl_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_ref, l_ref, acc_ref, *, causal, window, scale, half,
                block_length, kinds):
    """The flash recurrence, one grid step a live tile. Grid (B*H, visits):
    step ``t`` of a head reads, from the scalar-prefetched
    :func:`_visit_table`, its query tile ``qt_ref[t]``, its key tile
    ``kt_ref[t]`` (the index maps fetch by the same tables, so no step is
    without work and no tile without a live pair is fetched) and its flags:
    the step that opens a query tile's walk resets the running state, the
    one that closes it writes the output and the logsumexp, and only a step
    whose tile the mask cuts builds the mask (``kinds``: which of the two
    bodies, masked and not, the table asks for at all).

    A step holds the (BQ, d) query tile and a (BK, d) key/value tile and
    computes the tile whole (on the chip a key tile walked in slices of 256
    or 128 keys lost 20-50%: PERF.md, PR 34): scores (BQ, BK) in float32 from
    operands in their own dtype, the running max ``m`` and sum ``l`` as
    (BQ, 128) values with a row's lanes all equal from load to store
    (reductions keep their dimension and are broadcast over lanes: no value
    with the rows along lanes anywhere in the body, each of which cost a
    relayout a tile), the unnormalised output (BQ, d) in float32.
    A dead pair's score is ``_NEG``; a row that has met dead pairs only holds
    ``m == _NEG`` and a finite sum of ones, which the first live pair's
    ``alpha = exp(_NEG - m_new) == 0`` wipes, and every row of these masks
    sees a key (itself, or its own block).
    Beside the output it writes each row's logsumexp of the scaled scores
    over the keys it sees, ``m + log(l)`` in float32: what the backward
    needs to rebuild the probabilities without a pass of its own.
    ``lse_ref`` is one head's (n_q, 1, BQ), resident while the head's query
    tiles run; tile ``i`` fills row ``i`` of it."""
    t = pl.program_id(1)
    i, kb, flags = qt_ref[t], kt_ref[t], fl_ref[t]
    bq, bk, d = q_ref.shape[1], k_ref.shape[1], q_ref.shape[2]

    @pl.when(flags & _OPENS != 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # bfloat16 operands are exact in one MXU pass; an ambient "highest"
    # would ask Mosaic for a float32 matmul of them, which it refuses
    one_pass = jax.lax.Precision.DEFAULT \
        if q_ref.dtype == jnp.bfloat16 else None

    def tile(masked):
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())), precision=one_pass,
            preferred_element_type=jnp.float32) * scale     # (BQ, BK)
        if masked:
            # a column of rows against a row of keys: the mask's arithmetic
            # on positions runs over BQ + BK numbers, its last comparisons
            # alone over the tile
            rows = jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
            cols = jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
            s = jnp.where(
                _band_mask(i * bq + rows, kb * bk + cols, causal, window,
                           block_length, half), s, _NEG)
        m_prev = m_ref[...]                                 # (BQ, 128)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - _lanes(m_new, bk))
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * _lanes(alpha, d) + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            precision=one_pass, preferred_element_type=jnp.float32)

    for masked in kinds:
        pl.when((flags & _CUT != 0) == masked)(
            functools.partial(tile, masked))

    @pl.when(flags & _CLOSES != 0)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)      # (BQ, 128), lanes equal
        o_ref[0] = (acc_ref[...] / _lanes(l, d)).astype(o_ref.dtype)
        # the rows lie along sublanes here (every lane of a row equal) and
        # along lanes in the output: 128 rows at a time, keep the diagonal
        # and add the sublanes up (exact: the other terms are zeros)
        lse = m_ref[...] + jnp.log(l)
        n = min(bq, 128)
        diagonal = jax.lax.broadcasted_iota(jnp.int32, (n, 128), 0) \
            == jax.lax.broadcasted_iota(jnp.int32, (n, 128), 1)
        for r in range(0, bq, n):
            lse_ref[0, i, :, r:r + n] = jnp.sum(
                jnp.where(diagonal, lse[r:r + n], 0.0), axis=0,
                keepdims=True)[:, :n]


def _gqa_tiles(s, block_q, block_k, block_length):
    """``(bq, bk)``: under the block-diffusion mask the tiles are those of
    a half."""
    tiled = s // 2 if block_length else s
    return _pick_block(tiled, block_q), _pick_block(tiled, block_k)


def _gqa_pallas(q, k, v, causal, window, scale, block_q, block_k,
                interpret, block_length=0):
    b, h, s, d = q.shape
    hkv = k.shape[1]
    group = h // hkv
    bq, bk = _gqa_tiles(s, block_q, block_k, block_length)
    n_q, n_k = s // bq, s // bk
    table = _visit_table(n_q, n_k, bq, bk, causal, window, block_length)
    cut = table[2] & _CUT != 0
    kernel = functools.partial(
        _gqa_kernel, causal=causal, window=window, scale=scale, half=s // 2,
        block_length=block_length,
        kinds=tuple(m for m in (False, True) if (cut == m).any()))

    def q_index(bh, t, qt, kt, fl):
        return bh, qt[t], 0

    def kv_index(bh, t, qt, kt, fl):
        return bh // group, kt[t], 0

    grid = (b * h, len(cut))
    profiler.count("attention.kernel_grid_steps", grid[0] * grid[1])
    profiler.count("attention.kernel_live_tiles", b * h * len(cut))
    out, lse = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, bq, d), q_index),
                pl.BlockSpec((1, bk, d), kv_index),
                pl.BlockSpec((1, bk, d), kv_index),
            ],
            out_specs=[
                pl.BlockSpec((1, bq, d), q_index),
                pl.BlockSpec((1, n_q, 1, bq),
                             lambda bh, t, qt, kt, fl: (bh, 0, 0, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((bq, 128), jnp.float32),  # running max m
                pltpu.VMEM((bq, 128), jnp.float32),  # running normalizer l
                pltpu.VMEM((bq, d), jnp.float32),    # unnormalized output
            ]),
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, n_q, 1, bq), jnp.float32),
        ],
        interpret=interpret,
        name="gqa_block_diffusion_attention" if block_length
        else "gqa_flash_attention",
    )(*(jnp.asarray(x) for x in table), q.reshape(b * h, s, d),
      k.reshape(b * hkv, s, d), v.reshape(b * hkv, s, d))
    return out.reshape(b, h, s, d), lse.reshape(b, h, s)


def _gqa_blockwise_bwd(q, k, v, out, lse, do, causal, window, scale, block,
                       block_length=0):
    """The backward over the same schedule, in jnp: an outer scan over
    query blocks, and for each ONE loop over its run of :func:`_visit_table`
    (in the closed form of :func:`_run`, held to the table at trace time), so
    the temporaries are one (B, H, BQ, BK) tile and no key block without a
    live pair is touched. ``lse`` (B, H, S) float32 is the forward kernel's
    row logsumexp: the probabilities are ``exp(scores - lse)``, so the scores
    are computed once here (five matmuls and one ``exp`` a tile) and not a
    second time to find their normalizer. Matmul operands stay in the
    inputs' dtype with float32 accumulation."""
    b, h, s, d = q.shape
    hkv = k.shape[1]
    g = h // hkv
    blk = _gqa_tiles(s, block, block, block_length)[0]
    n = s // blk
    q5 = q.reshape(b, hkv, g, s, d)
    do5 = do.reshape(b, hkv, g, s, d)
    lse4 = lse.reshape(b, hkv, g, s)
    delta = jnp.sum(do5.astype(jnp.float32)
                    * out.reshape(b, hkv, g, s, d).astype(jnp.float32), -1)
    rows = jnp.arange(blk)
    _checked_run(blk, n, causal, window, block_length)

    def probs(qi, kj, i, j, lse_i):
        sc = jnp.einsum("bkgqd,bkcd->bkgqc", qi, kj,
                        preferred_element_type=jnp.float32) * scale
        mask = _band_mask((i * blk + rows)[:, None],
                          (j * blk + rows)[None, :], causal, window,
                          block_length, s // 2)
        if mask is not None:
            sc = jnp.where(mask, sc, _NEG)
        p = jnp.exp(sc - lse_i[..., None])
        return p if mask is None else jnp.where(mask, p, 0.0)

    def key_block(x, j):
        return jax.lax.dynamic_slice_in_dim(x, j * blk, blk, axis=2)

    def query_block(carry, i):
        dk, dv = carry
        qi = jax.lax.dynamic_slice_in_dim(q5, i * blk, blk, axis=3)
        doi = jax.lax.dynamic_slice_in_dim(do5, i * blk, blk, axis=3)
        di = jax.lax.dynamic_slice_in_dim(delta, i * blk, blk, axis=3)
        lse_i = jax.lax.dynamic_slice_in_dim(lse4, i * blk, blk, axis=3)

        lo, hi, key_of = _run(i, blk, n, causal, window, block_length)

        def grad_step(t, c):
            j = key_of(t)
            dqi, dk, dv = c
            kj, vj = key_block(k, j), key_block(v, j)
            p = probs(qi, kj, i, j, lse_i)
            dp = jnp.einsum("bkgqd,bkcd->bkgqc", doi, vj,
                            preferred_element_type=jnp.float32)
            ds = (p * (dp - di[..., None]) * scale).astype(q.dtype)
            dqi = dqi + jnp.einsum("bkgqc,bkcd->bkgqd", ds, kj,
                                   preferred_element_type=jnp.float32)
            dk_j = jnp.einsum("bkgqc,bkgqd->bkcd", ds, qi,
                              preferred_element_type=jnp.float32)
            dv_j = jnp.einsum("bkgqc,bkgqd->bkcd", p.astype(do.dtype), doi,
                              preferred_element_type=jnp.float32)
            dk = jax.lax.dynamic_update_slice_in_dim(
                dk, key_block(dk, j) + dk_j, j * blk, axis=2)
            dv = jax.lax.dynamic_update_slice_in_dim(
                dv, key_block(dv, j) + dv_j, j * blk, axis=2)
            return dqi, dk, dv

        dqi, dk, dv = jax.lax.fori_loop(
            lo, hi, grad_step,
            (jnp.zeros((b, hkv, g, blk, d), jnp.float32), dk, dv))
        return (dk, dv), dqi.astype(q.dtype)

    zeros = jnp.zeros((b, hkv, s, d), jnp.float32)
    (dk, dv), dq = jax.lax.scan(query_block, (zeros, zeros), jnp.arange(n))
    dq = jnp.moveaxis(dq, 0, 3).reshape(b, h, s, d)   # (n,b,k,g,blk,d)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _gqa_attention(q, k, v, causal, window, scale, block, interpret,
                   block_length):
    return _gqa_pallas(q, k, v, causal, window, scale, block, block,
                       interpret, block_length)[0]


def _gqa_fwd(q, k, v, causal, window, scale, block, interpret,
             block_length):
    """The residual contract: ``(q, k, v, out, lse)``. ``out`` and ``lse``
    carry the name a block's checkpoint keeps (``keep_residual``), and the
    NAMED values are both the primal output and the residuals: a name put
    on the output outside this function would mark another value, and the
    backward's ``out`` would be the kernel run again."""
    out, lse = _gqa_pallas(q, k, v, causal, window, scale, block, block,
                           interpret, block_length)
    if block_length:
        profiler.count("attention.block_diffusion_layers")
    out, lse = keep_residual(out), keep_residual(lse)
    return out, (q, k, v, out, lse)


def _gqa_bwd(causal, window, scale, block, interpret, block_length, res, ct):
    q, k, v, out, lse = res
    return _gqa_blockwise_bwd(q, k, v, out, lse, ct, causal, window, scale,
                              block, block_length)


_gqa_attention.defvjp(_gqa_fwd, _gqa_bwd)


def grouped_query_attention(q, k, v, causal=True, window=0, scale=None,
                            block=512, force_pallas=False, block_length=0):
    """Attention of H query heads over Hkv <= H key/value heads: q
    (B, H, S, D), k/v (B, Hkv, S, D), H a multiple of Hkv; ``window`` > 0
    (causal only) keeps keys i - window < j <= i; ``block_length`` > 0
    (causal, no window) is the block-diffusion training mask over a
    sequence of two halves, a noisy copy of a document and then the clean
    one, in blocks of ``block_length`` (:func:`_band_mask`): it has to
    divide the kernel's tile, and the tile a half.

    On a TPU the forward is the flash kernel run over the mask's live tiles
    alone: its grid is (B*H, visits), one step a tile that holds a pair that
    sees, by a table built on the host from the shapes and prefetched as
    scalars (:func:`_visit_table`: 136 visits a head for 16 causal tiles
    where the square has 256, 31 under a window of one tile, 288 for the
    block-diffusion walk over 2 x 16), so key blocks above the diagonal,
    behind the window or outside the walk are neither fetched nor computed
    nor stepped over, and the mask is built only in the tiles it cuts. The
    backward is the blockwise jnp recurrence over the same table. The forward
    hands the backward ``(q, k, v, out, lse)``, ``lse`` the kernel's row
    logsumexp (B, H, S) in float32, so a training step computes the scores
    twice: once in the kernel, once in the backward.
    ``out`` and ``lse`` are named for the executor's block checkpoint
    (``ops.registry.keep_residual``), which keeps them where it recomputes
    the rest of a layer: ``out`` is one activation in size and the dearest
    operation of the layer to compute again, and ``lse`` is a 64th of it.
    Elsewhere it is :func:`gqa_attention_reference`, differentiated
    by JAX, which names nothing; ``force_pallas`` runs kernel and backward
    through the Pallas interpreter (tests)."""
    b, h, s, d = q.shape
    if k.shape != v.shape or k.shape[0] != b or k.shape[2:] != (s, d) \
            or h % k.shape[1]:
        raise ValueError(
            f"grouped_query_attention: q {q.shape} against k {k.shape}, "
            f"v {v.shape}: same batch, length and head size, and a number "
            f"of query heads that is a multiple of the key/value heads")
    if window and not causal:
        raise ValueError("a window is causal: window > 0 needs causal=True")
    if block_length:
        tile = _pick_block(s // 2, block)
        if not causal or window or s % 2 or tile % block_length:
            raise ValueError(
                f"block_length {block_length} over {s} positions: the "
                f"block-diffusion mask needs causal=True, window=0, an "
                f"even length (two halves) and a block length that divides "
                f"the kernel's tile of {tile}")
    scale = 1.0 / (d ** 0.5) if scale is None else float(scale)
    on_tpu = jax.default_backend() == "tpu"
    if not on_tpu and not force_pallas:
        return gqa_attention_reference(q, k, v, causal, window, scale,
                                       int(block_length))
    return _gqa_attention(q, k, v, bool(causal), int(window), scale,
                          int(block), not on_tpu, int(block_length))


@register("_contrib_flash_attention", aliases=["flash_attention_op"],
          num_inputs=3, input_names=["query", "key", "value"],
          attrs=AttrSpec(causal=("bool", False), scale=("any", None)))
def _flash_attention_op(q, k, v, causal=False, scale=None):
    """Memory-efficient exact attention over (B, H, S, D) inputs
    (beyond-reference op: the 2017 reference predates attention kernels)."""
    return flash_attention(q, k, v, causal,
                           None if scale is None else float(scale))
