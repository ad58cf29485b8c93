"""LFM2-8B-A1B (Liquid AI, 8.3B-A1.5B) for the benchmark, as one chip of a
four-chip expert-parallel deployment trains it: the program under test built
through its users' entry, weights, buffers and batches from the seed, the
operation count, and the plain float32 reference.

From the program this file takes ``models.get_symbol("decoder_lm", cfg=...)``,
``SPMDTrainer`` with ``mx.optimizer.Adam`` and ``make_mesh`` and nothing else;
the reference half (``reference`` and the functions under it) imports nothing
of the program and takes nothing it made: weights, buffers and batches are
regenerated from the seed, and the layer equations are written out again
here, in ``jax.numpy``, with no kernel and no sort.

The layer equations, as the published implementation of the family has them
(x is S x D per document, D = 2048; no bias anywhere; RMSNorm with a learned
gain, eps 1e-5). Every layer: x = x + operator(RMSNorm_op(x)); x = x +
ffn(RMSNorm_ffn(x)); after the last layer RMSNorm and the head.

* gated short convolution (``conv`` layers), u = RMSNorm_op(x): [b, c, h] =
  split(u W_in^T, 3) with W_in (3D, D); g = b * h; y_t = w[:, 0] g_{t-2} +
  w[:, 1] g_{t-1} + w[:, 2] g_t a channel, w (D, 3), g_s = 0 for s < 0 in
  every row of the batch (a causal depthwise cross-correlation, left padding
  L - 1, no bias); out = (c * y) W_out^T, W_out (D, D). No activation inside.
* attention (``full_attention`` layers): q (32 x 64), k and v (8 x 64); q and
  k each RMS-normalised over the 64 dims of a head with a learned gain (64,),
  eps 1e-5, BEFORE the rotary embedding; rotary over all 64 dims, theta 1e6,
  dimension i paired with i + 32; causal softmax of q k^T / 8, query head h
  reading key/value head h // 4; o projection (D, D). No gate, no window.
* routed layer, z = RMSNorm_ffn(x): s = sigmoid(z W_r^T) over all 32 experts
  in float32; the 4 experts with the largest s + bias are chosen, bias (32,)
  a buffer that no gradient moves; their weights are the UNBIASED s_e / (sum
  of the chosen s + 1e-6, the file's norm_topk_eps), times
  routed_scaling_factor 1; each expert W_2
  (silu(W_1 z) * W_3 z), width 1792; the sum runs over the chosen experts
  HELD HERE (0-7 of the 32). Dense layers: the same SwiGLU at width 7168.
* head: the embedding's matrix (tied), over the held rows; mean next-token
  cross-entropy.

Departures, each also under ``assumed`` in the configuration's file: the head
is tied (the catalog's copy of the config drops the key; tied the uncut model
counts 8.34 B against the published 8.3 B); q/k norm (the family has it, the
config no key); ``expert_bias`` is drawn from the seed, N(0, 0.1), and fixed
(the config gives no update rule); what the 24 absent experts would add is
left out, here and in the program alike. Adam as ``mx.optimizer.Adam`` runs
it: lr_t = lr sqrt(1 - b2^t) / (1 - b1^t), w -= lr_t m / (sqrt(v) + eps).
"""
import functools
import json
import math

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from perfbench.seeded import OPERAND, seed_key

HIGHEST = lax.Precision.HIGHEST


# -- sizes and names ----------------------------------------------------------

def uncut(cfg):
    """The published configuration: the cut keys at their published values,
    the MLP kinds from ``num_dense_layers`` again."""
    whole = dict(cfg, **cfg["published"])
    whole.pop("mlp_layer_types", None)
    return whole


def _layers(cfg):
    """(mixer kind, MLP kind) of the first ``num_hidden_layers`` layers."""
    n = cfg["num_hidden_layers"]
    dense = cfg.get("num_dense_layers", n)
    mlps = cfg.get("mlp_layer_types") \
        or ["dense"] * min(dense, n) + ["sparse"] * max(n - dense, 0)
    return list(zip(cfg["layer_types"][:n], mlps[:n]))


def param_shapes(cfg):
    """name -> shape, under the names and in the layouts of the program's
    arguments: ``FullyConnected`` weights (out, in), the held experts'
    stacked (Eh, in, out). The head has no entry: it is the embedding."""
    D, d = cfg["hidden_size"], cfg["head_dim"]
    H, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    shapes = {"embed_weight": (cfg["vocab_size"], D)}
    for k, (kind, mlp) in enumerate(_layers(cfg)):
        p = f"layer{k}_"
        if kind == "conv":
            shapes[p + "conv_norm_gamma"] = (D,)
            shapes[p + "conv_in_weight"] = (3 * D, D)
            shapes[p + "conv_conv_weight"] = (D, cfg["conv_L_cache"])
            shapes[p + "conv_out_weight"] = (D, D)
        else:
            shapes[p + "attn_norm_gamma"] = (D,)
            shapes[p + "q_weight"] = (H * d, D)
            shapes[p + "k_weight"] = (kv * d, D)
            shapes[p + "q_norm_gamma"] = (d,)
            shapes[p + "k_norm_gamma"] = (d,)
            shapes[p + "v_weight"] = (kv * d, D)
            shapes[p + "o_weight"] = (D, H * d)
        shapes[p + "mlp_norm_gamma"] = (D,)
        if mlp == "dense":
            F = cfg["intermediate_size"]
            shapes[p + "mlp_gate_weight"] = (F, D)
            shapes[p + "mlp_up_weight"] = (F, D)
            shapes[p + "mlp_down_weight"] = (D, F)
        else:
            f, held = cfg["moe_intermediate_size"], cfg["num_experts_held"]
            shapes[p + "moe_router_weight"] = (cfg["num_experts"], D)
            shapes[p + "moe_expert_gate_weight"] = (held, D, f)
            shapes[p + "moe_expert_up_weight"] = (held, D, f)
            shapes[p + "moe_expert_down_weight"] = (held, f, D)
    shapes["final_norm_gamma"] = (D,)
    return shapes


def _init_leaf(key, k, name, shape, std):
    if name.endswith("gamma"):
        return jnp.ones(shape, jnp.float32)
    return std * jax.random.normal(jax.random.fold_in(key, k), shape,
                                   jnp.float32)


def init_params(cfg, seed):
    """float32 master weights from the seed in one jitted call on the
    default device: N(0, init_std) everywhere, the norms' gains 1."""
    shapes = param_shapes(cfg)
    std = float(cfg["init_std"])

    @jax.jit
    def make(key):
        return {name: _init_leaf(key, k, name, shape, std)
                for k, (name, shape) in enumerate(shapes.items())}

    return make(seed_key(seed))


def init_buffers(cfg, seed):
    """The routed layers' selection bias, one (E,) float32 vector a layer
    under the program's name for it: N(0, expert_bias_std) from the seed,
    and fixed (no gradient, no optimizer, no update rule)."""
    key = jax.random.fold_in(seed_key(seed), 0xB1A5)
    std = float(cfg["expert_bias_std"])
    return {f"layer{k}_moe_expert_bias": std * jax.random.normal(
        jax.random.fold_in(key, k), (cfg["num_experts"],), jnp.float32)
        for k, (_, mlp) in enumerate(_layers(cfg)) if mlp == "sparse"}


def make_batches(cfg, traffic, seed, count=None):
    """The cell's distinct host batches: one document a row, its token ids
    Zipf-distributed over the held rows of the vocabulary (p(id r - 1) ~
    r^-exponent); labels are the ids shifted by one."""
    rows = traffic["per_chip_batch"] * traffic["chips"]
    S, V = traffic["seq_len"], cfg["vocab_size"]
    p = np.arange(1, V + 1, dtype=np.float64) ** -float(
        traffic["zipf_exponent"])
    p /= p.sum()
    rng = np.random.default_rng([int(seed), 0xDA7A])
    out = []
    for _ in range(count or traffic["distinct_batches"]):
        ids = rng.choice(V, size=(rows, S + 1), p=p).astype(np.float32)
        out.append((ids[:, :-1].copy(), ids[:, 1:].copy()))
    return out


def items_per_batch(cfg, traffic):
    return traffic["per_chip_batch"] * traffic["chips"] * traffic["seq_len"]


def flops_per_item(cfg):
    """Training FLOPs of one token at ``flops_seq_len`` positions a
    document: 3 x forward, 2 per multiply-add, nothing recomputed counted.
    Forward multiply-adds of a layer: the short convolution's two
    projections (3 D^2 + D^2) and its taps (L a channel), or the q, k, v
    and o projections with scores and values over the keys a query sees on
    average (the causal half, S / 2); then the dense SwiGLU, or the router
    over all experts and the routed experts at the even load (top_k x held
    / experts of them a token). Then the head over the held rows. Norms,
    rotary, softmax, the two gates and the gather of the embedding are left
    out."""
    S, D, d = cfg["flops_seq_len"], cfg["hidden_size"], cfg["head_dim"]
    H, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    macs = 0.0
    for kind, mlp in _layers(cfg):
        if kind == "conv":
            macs += 4 * D * D + cfg["conv_L_cache"] * D
        else:
            macs += 2 * D * H * d + 2 * D * kv * d + 2 * H * d * S / 2
        if mlp == "dense":
            macs += 3 * D * cfg["intermediate_size"]
        else:
            routed = cfg["num_experts_per_tok"] * cfg["num_experts_held"] \
                / cfg["num_experts"]
            macs += D * cfg["num_experts"] \
                + routed * 3 * D * cfg["moe_intermediate_size"]
    macs += D * cfg["vocab_size"]
    return 3 * 2 * macs


# -- the program under test ----------------------------------------------------

@jax.jit
def _leaf_norms(tree):
    return {n: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for n, v in tree.items()}


@functools.partial(jax.jit, static_argnames=("names", "std"))
def _delta_norms(now, key, names, std):
    """Per-leaf norm of (leaf now - leaf at the seed), the seed's leaves
    made again inside the one program, none kept. ``names`` is the order of
    ``param_shapes``, which numbers the leaves' keys (a dict argument
    arrives sorted)."""
    return _leaf_norms({
        name: now[name] - _init_leaf(key, k, name, now[name].shape, std)
        for k, name in enumerate(names)})


# the routed layers' counters, as ``MoEFFN`` declares them
ROUTED = ("moe.assignments_held", "moe.load_max", "moe.overflow")


class Program:
    """``SPMDTrainer`` bound over the decoder's symbol with Adam, the seed's
    weights and the seed's selection bias: the one object the checked steps
    and the window both drive through ``fit``."""

    input_names = ("data", "softmax_label")

    def __init__(self, cfg, traffic, seed, devices):
        import mxnet_tpu as mx
        from mxnet_tpu import models
        from mxnet_tpu.parallel import SPMDTrainer, make_mesh

        if traffic["seq_len"] != cfg["flops_seq_len"]:
            raise SystemExit(
                f"the traffic's seq_len {traffic['seq_len']} is not the "
                f"configuration's flops_seq_len {cfg['flops_seq_len']}, at "
                f"which its operations are counted")
        self.cfg, self.traffic, self._seed = cfg, traffic, seed
        self._count, self._routed = mx.profiler.count, None
        chips = traffic["chips"]
        self.mesh = make_mesh(dict(traffic["mesh"]),
                              devices=list(devices)[:chips])
        rows, S = traffic["per_chip_batch"] * chips, traffic["seq_len"]
        # a program that cannot express the configuration refuses it here,
        # by name (``decoder_lm: unknown layer type 'conv'``)
        self.sym = models.get_symbol("decoder_lm", cfg=cfg)
        opt = cfg["optimizer"]
        self.beta1 = float(opt["beta1"])
        self.trainer = SPMDTrainer(
            self.sym, optimizer=mx.optimizer.Adam(
                learning_rate=float(opt["learning_rate"]), beta1=self.beta1,
                beta2=float(opt["beta2"]), epsilon=float(opt["epsilon"]),
                wd=float(opt["wd"])),
            mesh=self.mesh, compute_dtype=cfg["compute_dtype"])
        host = jax.device_get(init_params(cfg, seed))
        bias = jax.device_get(init_buffers(cfg, seed))
        aux = {n: bias[n] if n in bias else np.zeros((3,), np.float32)
               for n in self.sym.list_auxiliary_states()}
        self.trainer.bind(
            data_shapes={"data": (rows, S)},
            label_shapes={"softmax_label": (rows, S)}, arg_params=host,
            aux_params=aux)

    def input_shardings(self):
        from jax.sharding import NamedSharding, PartitionSpec as P
        return {n: NamedSharding(self.mesh, P("data"))
                for n in self.input_names}

    def fit(self, feed, on_batch_end=None):
        self.trainer.fit(feed, num_epoch=1, batch_end_callback=on_batch_end)

    def sync(self):
        jax.block_until_ready(self.trainer.params)

    def step_loss(self, param, labels):
        """The step's own output: the mean next-token cross-entropy."""
        return float(np.asarray(param.locals["step_outs"][0]).reshape(()))

    def grad_norms(self):
        """Per-leaf norm of the gradient the optimizer got, from Adam's mean
        after the first step: m = (1 - beta1) g."""
        means = {n: s[0] for n, s in self.trainer.states.items()}
        return {n: float(v) / (1.0 - self.beta1)
                for n, v in jax.device_get(_leaf_norms(means)).items()}

    def delta_norms(self):
        """Per-leaf norm of (parameters now - parameters at the seed), the
        seed's leaf made again one at a time."""
        return {n: float(v) for n, v in jax.device_get(_delta_norms(
            dict(self.trainer.params), seed_key(self._seed),
            tuple(param_shapes(self.cfg)),
            float(self.cfg["init_std"]))).items()}

    def routed_counters(self):
        """The routed layers' counters since bind, summed over the layers:
        one boundary read of the device (``SPMDTrainer.aux_counters``)."""
        nodes = self.trainer.aux_counters().values()
        return {k: sum(node[k] for node in nodes) for k in ROUTED}

    def counters(self):
        """The step programs compiled. The driver calls this at the
        window's two ends and nowhere else, so each call is also the
        boundary read of the routed counters: what they grew by since the
        call before is added to the program's counters of the same names
        (``mx.profiler.count``), where the readers of the routing metrics
        find the window's share beside ``step.count``."""
        now = self.routed_counters()
        for k in ROUTED if self._routed is not None else ():
            self._count(k, int(now[k] - self._routed[k]))
        self._routed = now
        return {"step_programs": int(self.trainer.retrace_guard.count)}

    def close(self):
        self.trainer = None


# -- the plain reference ---------------------------------------------------------

def _e4m3(x):
    """x rounded to float8 e4m3 (3 mantissa bits, binades 2^-6 .. 2^8,
    subnormals under them, largest 448; round to nearest even) with one
    scale per tensor, amax onto 448: what ``perfbench.seeded``'s control
    does, in float32 arithmetic and not through the float8 type (which
    gives a NaN inside ``lax.map`` on the TPU v5e: PERF.md, PR 27)."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    mag = jnp.abs(x / scale)
    _, exponent = jnp.frexp(jnp.maximum(mag, 2.0 ** -6))   # mag = m 2^e
    step = jnp.exp2((jnp.minimum(exponent, 9) - 1 - 3).astype(jnp.float32))
    return jnp.sign(x) * jnp.minimum(jnp.round(mag / step) * step,
                                     448.0) * scale


@jax.custom_vjp
def _rounded_e4m3(x):
    return _e4m3(x)


_rounded_e4m3.defvjp(lambda x: (_e4m3(x), None),
                     lambda _, g: (_e4m3(g),))

# precision of the reference -> what it does to every matmul operand and
# activation: nothing, or the control's rounding (cotangents rounded alike)
_OPERAND = dict(OPERAND, fp8=_rounded_e4m3)

# the faults ``reference`` can plant
FAULTS = ("half_batch", "expert_out", "bias_out")


def _mm(x, w, operand):
    """x @ w.T at full precision, operands and result through the control's
    rounding."""
    return operand(jnp.dot(operand(x), operand(w).T, precision=HIGHEST))


def _rms(x, gain, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * gain


def _swiglu(z, w_gate, w_up, w_down, operand):
    return _mm(operand(jax.nn.silu(_mm(z, w_gate, operand))
                       * _mm(z, w_up, operand)), w_down, operand)


def _rotate(x, theta):
    """x (S, heads, d): every head rotated by the position over all d dims,
    dim i paired with i + d / 2, frequencies theta^(-2i/d)."""
    d = x.shape[-1]
    inv = theta ** (-2.0 * jnp.arange(d // 2, dtype=jnp.float32) / d)
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _short_conv(u, w_in, w_conv, w_out, operand):
    """u (S, D): the gated short convolution, the convolution an explicit
    sum over taps of the gated stream moved later by L - 1 - k positions,
    zeros entering at the document's start."""
    S, D = u.shape
    taps = w_conv.shape[-1]
    bch = _mm(u, w_in, operand)
    b, c, h = bch[:, :D], bch[:, D:2 * D], bch[:, 2 * D:]
    g = operand(b * h)
    padded = jnp.concatenate([jnp.zeros((taps - 1, D), g.dtype), g], 0)
    y = sum(padded[k:k + S] * w_conv[:, k] for k in range(taps))
    return _mm(operand(c * operand(y)), w_out, operand)


def _attend(q, k, v, operand, block):
    """q (S, kv, G, d), k and v (S, kv, d): softmax attention under an
    explicit causal mask, a block of query rows at a time against all the
    keys, each block rematerialised."""
    S, d = q.shape[0], q.shape[-1]
    block = block if S % block == 0 else S
    kpos = jnp.arange(S)

    @jax.checkpoint
    def rows(qb, qpos):
        s = jnp.einsum("qkgd,ckd->kgqc", qb, k, precision=HIGHEST) \
            / math.sqrt(d)
        seen = kpos[None, :] <= qpos[:, None]
        p = operand(jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1))
        return jnp.einsum("kgqc,ckd->qkgd", p, v, precision=HIGHEST)

    out = lax.map(lambda args: rows(*args),
                  (q.reshape((S // block, block) + q.shape[1:]),
                   kpos.reshape(S // block, block)))
    return out.reshape(q.shape)


def _routed(z, p, bias, prefix, cfg, operand, fault, chunk):
    """What the held experts add: for every token and every held expert,
    the router's weight (nought where the expert is not among the token's
    chosen) times the expert's output. No sort, no gather: a loop over the
    held experts, each over every token, a chunk of tokens at a time."""
    E, K = cfg["num_experts"], cfg["num_experts_per_tok"]
    held, off = cfg["num_experts_held"], cfg.get("expert_offset", 0)
    s = jax.nn.sigmoid(jnp.dot(operand(z), operand(
        p[prefix + "router_weight"]).T, precision=HIGHEST))
    # the planted fault 'bias_out': the bias left out of the selection
    _, idx = lax.top_k(s if fault == "bias_out" else s + bias, K)
    top = jnp.take_along_axis(s, idx, axis=-1)
    total = jnp.sum(top, -1, keepdims=True) + cfg.get("norm_topk_eps", 0.0)
    w = cfg["routed_scaling_factor"] * top / total
    weight = jnp.zeros((z.shape[0], E), jnp.float32).at[
        jnp.arange(z.shape[0])[:, None], idx].add(w)[:, off:off + held]
    if fault == "expert_out":
        # the planted fault: the held expert most tokens chose adds nothing
        fullest = jnp.argmax(jnp.sum(weight > 0, axis=0))
        weight = jnp.where(jnp.arange(held)[None, :] == fullest, 0.0, weight)
    wg, wu, wd = (operand(p[prefix + f"expert_{n}_weight"])
                  for n in ("gate", "up", "down"))

    @jax.checkpoint
    def experts(zc, wc):
        out = jnp.zeros_like(zc)
        for e in range(held):
            hidden = operand(jax.nn.silu(operand(jnp.dot(
                zc, wg[e], precision=HIGHEST))) * operand(jnp.dot(
                    zc, wu[e], precision=HIGHEST)))
            out = out + operand(jnp.dot(hidden * wc[:, e:e + 1], wd[e],
                                        precision=HIGHEST))
        return out

    T = z.shape[0]
    chunk = chunk if T % chunk == 0 else T
    out = lax.map(lambda zw: experts(*zw),
                  (operand(z).reshape(T // chunk, chunk, -1),
                   weight.reshape(T // chunk, chunk, -1)))
    return out.reshape(z.shape)


def _document_loss(cfg, p, buffers, ids, labels, operand, fault):
    """Summed next-token cross-entropy of one document (S,)."""
    eps, d = cfg["norm_eps"], cfg["head_dim"]
    H, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    ref = cfg["reference"]
    x = operand(p["embed_weight"])[ids.astype(jnp.int32)]
    S = x.shape[0]
    for k, (kind, mlp) in enumerate(_layers(cfg)):
        pre = f"layer{k}_"

        def conv(x, pre=pre):
            u = _rms(x, p[pre + "conv_norm_gamma"], eps)
            return x + _short_conv(u, p[pre + "conv_in_weight"],
                                   p[pre + "conv_conv_weight"],
                                   p[pre + "conv_out_weight"], operand)

        def attention(x, pre=pre):
            u = _rms(x, p[pre + "attn_norm_gamma"], eps)
            q = _mm(u, p[pre + "q_weight"], operand).reshape(S, H, d)
            key = _mm(u, p[pre + "k_weight"], operand).reshape(S, kv, d)
            q = _rotate(_rms(q, p[pre + "q_norm_gamma"], eps),
                        cfg["rope_theta"])
            key = _rotate(_rms(key, p[pre + "k_norm_gamma"], eps),
                          cfg["rope_theta"])
            v = _mm(u, p[pre + "v_weight"], operand).reshape(S, kv, d)
            out = _attend(operand(q).reshape(S, kv, H // kv, d),
                          operand(key), v, operand, ref["query_block"])
            return x + _mm(out.reshape(S, H * d), p[pre + "o_weight"],
                           operand)

        x = jax.checkpoint(conv if kind == "conv" else attention)(x)

        def mlp_block(x, pre=pre, mlp=mlp):
            z = _rms(x, p[pre + "mlp_norm_gamma"], eps)
            if mlp == "dense":
                return x + _swiglu(z, p[pre + "mlp_gate_weight"],
                                   p[pre + "mlp_up_weight"],
                                   p[pre + "mlp_down_weight"], operand)
            return x + _routed(z, p, buffers[pre + "moe_expert_bias"],
                               pre + "moe_", cfg, operand, fault,
                               ref["token_chunk"])

        x = jax.checkpoint(mlp_block)(x)
    x = _rms(x, p["final_norm_gamma"], eps)

    @jax.checkpoint
    def head(block, idx):
        logits = _mm(block, p["embed_weight"], operand)      # the tied head
        return -jnp.sum(jnp.take_along_axis(
            jax.nn.log_softmax(logits), idx[:, None], axis=1))

    chunk = ref["token_chunk"] if S % ref["token_chunk"] == 0 else S
    return jnp.sum(lax.map(
        lambda xl: head(*xl),
        (x.reshape(S // chunk, chunk, -1),
         labels.astype(jnp.int32).reshape(S // chunk, chunk))))


def forward_loss(cfg, p, buffers, ids, labels, operand, fault=None):
    """Mean next-token cross-entropy over the batch's tokens, float32
    throughout, matmuls at ``highest``; one document after another, so the
    memory is one document's."""
    totals = lax.map(lambda il: _document_loss(cfg, p, buffers, il[0], il[1],
                                               operand, fault),
                     (ids, labels))
    return jnp.sum(totals) / ids.size


@functools.lru_cache(maxsize=None)
def _reference_step(cfg_json, precision, fault):
    cfg = json.loads(cfg_json)
    operand = _OPERAND[precision]
    opt = cfg["optimizer"]
    lr, b1, b2 = opt["learning_rate"], opt["beta1"], opt["beta2"]
    eps, wd = opt["epsilon"], opt["wd"]

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(p, mean, var, buffers, t, ids, labels):
        loss, g = jax.value_and_grad(
            lambda q: forward_loss(cfg, q, buffers, ids, labels, operand,
                                   fault))(p)
        norms = {n: jnp.sqrt(jnp.sum(jnp.square(v))) for n, v in g.items()}
        lr_t = lr * jnp.sqrt(1.0 - b2 ** t) / (1.0 - b1 ** t)
        new_p, new_mean, new_var = {}, {}, {}
        for n in p:
            grad = g[n] + wd * p[n]
            new_mean[n] = b1 * mean[n] + (1.0 - b1) * grad
            new_var[n] = b2 * var[n] + (1.0 - b2) * jnp.square(grad)
            new_p[n] = p[n] - lr_t * new_mean[n] \
                / (jnp.sqrt(new_var[n]) + eps)
        return loss, new_p, new_mean, new_var, norms

    return step


def reference(cfg, traffic, seed, precision="float32", fault=None,
              devices=None):
    """The first ``check_steps`` steps from the seed in plain jnp: loss of
    each step, per-leaf norm of the first gradient, per-leaf norm of the
    parameters' change after the last. It is given the program's share:
    the held experts, the held rows of the vocabulary. ``precision`` other
    than float32 is the control. ``fault='half_batch'`` leaves half of the
    tokens out (half the rows, or with one row half its positions) and
    takes the mean over the rest; ``fault='expert_out'`` leaves out, in
    every routed layer, the output of the held expert most tokens chose;
    ``fault='bias_out'`` chooses the experts by the scores alone, the bias
    left out of the selection."""
    if fault not in (None,) + FAULTS:
        raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")
    step = _reference_step(json.dumps(cfg, sort_keys=True), precision,
                           fault if fault != "half_batch" else None)
    p = init_params(cfg, seed)
    buffers = init_buffers(cfg, seed)
    mean = jax.tree_util.tree_map(jnp.zeros_like, p)
    var = jax.tree_util.tree_map(jnp.zeros_like, p)
    losses, grad_norms = [], None
    batches = make_batches(cfg, traffic, seed, traffic["check_steps"])
    for k, (x, y) in enumerate(batches):
        if fault == "half_batch":
            if len(x) > 1:
                x, y = x[:len(x) // 2], y[:len(y) // 2]
            else:
                x, y = x[:, :x.shape[1] // 2], y[:, :y.shape[1] // 2]
        with jax.default_matmul_precision("highest"):
            loss, p, mean, var, norms = step(
                p, mean, var, buffers, jnp.float32(k + 1), jnp.asarray(x),
                jnp.asarray(y))
        losses.append(float(loss))
        if k == 0:
            grad_norms = {n: float(v)
                          for n, v in jax.device_get(norms).items()}
    delta = {n: float(v) for n, v in jax.device_get(_delta_norms(
        p, seed_key(seed), tuple(param_shapes(cfg)),
        float(cfg["init_std"]))).items()}
    return {"losses": losses, "grad_norms": grad_norms, "delta_norms": delta}
