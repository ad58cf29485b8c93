"""Pallas kernels vs jnp references (interpret mode on the CPU mesh;
the real MXU path is exercised by the TPU verify/bench flows)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.ops.pallas.attention import _attn_reference, flash_attention
from mxnet_tpu.ops.pallas.lstm import lstm_cell_fused


def _qkv(b=1, h=2, s=128, d=32, seed=0, sk=None):
    rng = np.random.RandomState(seed)
    q = rng.normal(0, 1, (b, h, s, d)).astype(np.float32)
    k = rng.normal(0, 1, (b, h, sk or s, d)).astype(np.float32)
    v = rng.normal(0, 1, (b, h, sk or s, d)).astype(np.float32)
    return jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_interpret_matches_reference(causal):
    q, k, v = _qkv(s=128)
    ref = _attn_reference(q, k, v, causal, 1.0 / np.sqrt(q.shape[-1]))
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64,
                          force_pallas=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_odd_blocks():
    # S not divisible by the target block sizes -> _pick_block shrinks
    q, k, v = _qkv(s=96, seed=1)
    ref = _attn_reference(q, k, v, True, 1.0 / np.sqrt(q.shape[-1]))
    out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                          force_pallas=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_grad_matches_reference():
    q, k, v = _qkv(s=64, seed=2)

    def loss_flash(q, k, v):
        return flash_attention(q, k, v, causal=True,
                               force_pallas=True).sum()

    def loss_ref(q, k, v):
        return _attn_reference(q, k, v, True,
                               1.0 / np.sqrt(q.shape[-1])).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


def test_flash_attention_contrib_op():
    q, k, v = _qkv(s=32, seed=3)
    out = nd.contrib.flash_attention(nd.array(np.asarray(q)),
                                     nd.array(np.asarray(k)),
                                     nd.array(np.asarray(v)), causal=True)
    ref = _attn_reference(q, k, v, True, 1.0 / np.sqrt(q.shape[-1]))
    np.testing.assert_allclose(out.asnumpy(), np.asarray(ref), rtol=2e-5,
                               atol=2e-5)


def test_flash_attention_causal_rejects_longer_queries():
    q, k, v = _qkv(s=64, sk=32, seed=7)
    with pytest.raises(ValueError):
        flash_attention(q, k, v, causal=True, force_pallas=True)


@pytest.mark.parametrize("n,hd,dtype,tol,block", [
    (8, 16, np.float32, 1e-5, None),      # whole-array kernel (H % 128 != 0)
    (8, 200, jnp.bfloat16, 2e-2, None),   # whole-array kernel, bf16 operands
    (8, 1024, np.float32, 1e-4, 256),     # 4 grid steps of 256 hidden units
    (16, 256, jnp.bfloat16, 2e-2, 256),   # native bf16 projection, f32 gates
], ids=["h16-f32-whole", "h200-bf16-whole", "h1024-f32-tiled", "h256-bf16"])
def test_lstm_cell_interpret_matches_jnp(n, hd, dtype, tol, block):
    from mxnet_tpu.ops.pallas.lstm import _hidden_block
    rng = np.random.RandomState(4)
    scale = 0.5 / np.sqrt(hd / 16)

    def arr(shape, s=1.0):
        return jnp.asarray(rng.normal(0, s, shape).astype(np.float32)
                           ).astype(dtype)

    xproj, h, c = arr((n, 4 * hd)), arr((n, hd)), arr((n, hd))
    w = arr((4 * hd, hd), scale)
    assert _hidden_block(hd) == block
    h_j, c_j = lstm_cell_fused(xproj, h, c, w, impl="jnp")
    h_p, c_p = lstm_cell_fused(xproj, h, c, w, impl="interpret")
    for got, want in ((h_p, h_j), (c_p, c_j)):
        assert got.dtype == want.dtype
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=tol, atol=tol)


def test_lstm_cell_custom_vjp_matches_autodiff():
    rng = np.random.RandomState(5)
    n, hd = 4, 8
    args = [jnp.asarray(rng.normal(0, 0.7, s).astype(np.float32))
            for s in [(n, 4 * hd), (n, hd), (n, hd), (4 * hd, hd)]]

    def loss_fused(*a):
        hn, cn = lstm_cell_fused(*a, impl="jnp")  # custom vjp path
        return (hn * 2 + cn).sum()

    def plain_cell(xproj, h, c, w):
        g = xproj + h @ w.T
        i, f = jax.nn.sigmoid(g[:, :hd]), jax.nn.sigmoid(g[:, hd:2 * hd])
        gg, o = jnp.tanh(g[:, 2 * hd:3 * hd]), jax.nn.sigmoid(g[:, 3 * hd:])
        cn = f * c + i * gg
        return (o * jnp.tanh(cn) * 2 + cn).sum()

    gf = jax.grad(loss_fused, argnums=(0, 1, 2, 3))(*args)
    gr = jax.grad(plain_cell, argnums=(0, 1, 2, 3))(*args)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-5)


def test_rnn_op_still_trains_with_fused_cell():
    """End-to-end: the RNN op (now routed through lstm_cell_fused) keeps
    its gradients correct on the CPU backend."""
    rng = np.random.RandomState(6)
    t, n, input_size, hd = 5, 3, 4, 6
    from mxnet_tpu.ops.rnn_ops import rnn_param_size
    psize = rnn_param_size(1, input_size, hd, "lstm")
    x = mx.nd.array(rng.normal(0, 1, (t, n, input_size)).astype(np.float32))
    p = mx.nd.array(rng.normal(0, 0.3, (psize,)).astype(np.float32))
    h0 = mx.nd.zeros((1, n, hd))
    c0 = mx.nd.zeros((1, n, hd))
    p.attach_grad()
    with mx.autograd.record():
        out = nd.RNN(x, p, h0, c0, state_size=hd, num_layers=1, mode="lstm")
        loss = out.sum()
    loss.backward()
    g = p.grad.asnumpy()
    assert np.all(np.isfinite(g)) and np.abs(g).sum() > 0
