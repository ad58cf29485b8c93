"""Laguna-XS.2 (poolside, 33.4B-A3B) for the benchmark, as one chip of an
expert-parallel deployment trains it: the program under test built through
its users' entry, weights and batches from the seed, the operation count, and
the plain float32 reference.

From the program this file takes ``models.get_symbol("decoder_lm", cfg=...)``,
``SPMDTrainer`` with ``mx.optimizer.Adam`` and ``make_mesh`` and nothing else;
the reference half (``reference`` and the functions under it) imports nothing
of the program and takes nothing it made: weights and batches are regenerated
from the seed, and the layer equations are written out again here, in
``jax.numpy``, with no kernel and no sort.

The layer equations (x is S x D per document; no bias anywhere; RMSNorm with a
learned gain, eps 1e-6). Attention of layer l, H_l query heads (48 on full, 64
on sliding layers), 8 key/value heads of 128: u = RMSNorm(x); q, k, v = u W_q,
u W_k, u W_v; rotary embedding on q and k (sliding layers: all 128 dims, theta
10,000; full layers: the first 64 dims at YaRN's frequencies, cos and sin
times ``attention_factor``; dimension i pairs with i + r/2); query head h
reads key/value head h // (H_l / 8); scores q k^T / sqrt(128), causal, on
sliding layers key j seen from i only if i - 512 < j <= i; head h's output is
multiplied by sigmoid(u W_g)_h; y = x + concat(heads) W_o. Dense MLP (layer
0): y + (silu(z W_1) * (z W_3)) W_2 with z = RMSNorm(y). Sparse MLP: s =
sigmoid(z W_r) over all 256 experts in float32, the 8 largest, w = 2.5 s_e /
sum of the chosen s, y + sum of w_e E_e(z) over the chosen experts HELD HERE +
E_shared(z), each E a SwiGLU of 512. Final RMSNorm, untied head, mean
next-token cross-entropy. Adam as ``mx.optimizer.Adam`` runs it: lr_t = lr
sqrt(1 - b2^t) / (1 - b1^t), w -= lr_t m / (sqrt(v) + eps).
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
    """The published configuration: the cut keys at their published values."""
    return dict(cfg, **cfg["published"])


def _layers(cfg):
    n = cfg["num_hidden_layers"]
    return list(zip(cfg["layer_types"][:n],
                    cfg["num_attention_heads_per_layer"][:n],
                    cfg["mlp_layer_types"][:n]))


def param_shapes(cfg):
    """name -> shape, under the names and in the layouts of the program's
    arguments: ``FullyConnected`` weights (out, in), the held experts'
    stacked (Eh, in, out)."""
    D, d = cfg["hidden_size"], cfg["head_dim"]
    kv, V = cfg["num_key_value_heads"], cfg["vocab_size"]
    shapes = {"embed_weight": (V, D)}
    for k, (_, heads, mlp) in enumerate(_layers(cfg)):
        p = f"layer{k}_"
        shapes[p + "attn_norm_gamma"] = (D,)
        shapes[p + "q_weight"] = (heads * d, D)
        shapes[p + "k_weight"] = (kv * d, D)
        shapes[p + "v_weight"] = (kv * d, D)
        if cfg["gating"]:
            shapes[p + "gate_weight"] = (heads, D)
        shapes[p + "o_weight"] = (D, heads * d)
        shapes[p + "mlp_norm_gamma"] = (D,)
        if mlp == "dense":
            F = cfg["intermediate_size"]
            shapes[p + "mlp_gate_weight"] = (F, D)
            shapes[p + "mlp_up_weight"] = (F, D)
            shapes[p + "mlp_down_weight"] = (D, F)
        else:
            f, fs = cfg["moe_intermediate_size"], \
                cfg["shared_expert_intermediate_size"]
            held = cfg["num_experts_held"]
            shapes[p + "moe_router_weight"] = (cfg["num_experts"], D)
            shapes[p + "moe_expert_gate_weight"] = (held, D, f)
            shapes[p + "moe_expert_up_weight"] = (held, D, f)
            shapes[p + "moe_expert_down_weight"] = (held, f, D)
            shapes[p + "moe_shared_gate_weight"] = (fs, D)
            shapes[p + "moe_shared_up_weight"] = (fs, D)
            shapes[p + "moe_shared_down_weight"] = (D, fs)
    shapes["final_norm_gamma"] = (D,)
    shapes["lm_head_weight"] = (V, D)
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
    Forward multiply-adds of a layer: the q, k, v, gate and o projections;
    scores and values over the keys a query sees on average (full layers the
    causal half, S / 2; sliding layers the band, W - W^2 / 2S); the dense
    SwiGLU, or the router over all experts, the shared expert and the routed
    experts at the even load (top_k x held / experts of them a token). Then
    the head over the held rows. Norms, rotary, softmax and the gather of
    the embedding are left out."""
    S = cfg["flops_seq_len"]
    D, d = cfg["hidden_size"], cfg["head_dim"]
    kv, W = cfg["num_key_value_heads"], cfg["sliding_window"]
    macs = 0.0
    for kind, heads, mlp in _layers(cfg):
        macs += 2 * D * heads * d + 2 * D * kv * d
        macs += D * heads if cfg["gating"] else 0
        seen = S / 2 if kind == "full_attention" else W - W * W / (2 * S)
        macs += 2 * heads * d * seen
        if mlp == "dense":
            macs += 3 * D * cfg["intermediate_size"]
        else:
            routed = cfg["num_experts_per_tok"] * cfg["num_experts_held"] \
                / cfg["num_experts"]
            macs += D * cfg["num_experts"] \
                + 3 * D * cfg["shared_expert_intermediate_size"] \
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
    """``SPMDTrainer`` bound over the decoder's symbol with Adam and the
    seed's weights: the one object the checked steps and the window both
    drive through ``fit``."""

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
        self.trainer.bind(
            data_shapes={"data": (rows, S)},
            label_shapes={"softmax_label": (rows, S)}, arg_params=host,
            aux_params={n: np.zeros((3,), np.float32)
                        for n in self.sym.list_auxiliary_states()})

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
        find the window's share beside ``step.count``. (The driver sums its
        own ``counters`` into ``compiles_in_window``: they cannot ride
        there.)"""
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
    does, in float32 arithmetic and not through the float8 type. On the TPU
    v5e this reference's forward came out NaN under ``seeded.rounded``
    (inside the attention's ``lax.map``; the same code is finite on the CPU
    and agrees with this to the bit there): the control has to be a number,
    not a fault of a conversion the chip does not have."""
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


def _inv_frequencies(rope, r):
    """The r / 2 rotary frequencies of one kind of layer: theta^(-2i/r), and
    under YaRN each divided by ``factor`` where its wavelength does not fit
    the original context, with the linear ramp of the published recipe
    between ``beta_fast`` and ``beta_slow`` turns."""
    theta = float(rope["rope_theta"])
    i = jnp.arange(r // 2, dtype=jnp.float32)
    inv = theta ** (-2.0 * i / r)
    if rope.get("rope_type", "default") == "default":
        return inv
    orig = rope["original_max_position_embeddings"]

    def dim_of(turns):
        return r * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(dim_of(rope["beta_fast"])), 0)
    high = min(math.ceil(dim_of(rope["beta_slow"])), r - 1)
    ramp = jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return inv / rope["factor"] * ramp + inv * (1.0 - ramp)


def _rotate(x, rope, head_dim):
    """x (S, heads, d): the first r dims of each head rotated by the
    position, dim i paired with i + r / 2; cos and sin times
    ``attention_factor``."""
    r = int(round(head_dim * rope.get("partial_rotary_factor", 1)))
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] \
        * _inv_frequencies(rope, r)[None, :]
    factor = float(rope.get("attention_factor", 1.0))
    cos = (jnp.cos(angle) * factor)[:, None, :]
    sin = (jnp.sin(angle) * factor)[:, None, :]
    a, b, rest = x[..., :r // 2], x[..., r // 2:r], x[..., r:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest], -1)


def _attend(q, k, v, window, operand, block):
    """q (S, kv, G, d), k and v (S, kv, d): softmax attention under an
    explicit causal (and window) mask, a block of query rows at a time
    against all the keys, each block rematerialised."""
    S, d = q.shape[0], q.shape[-1]
    block = block if S % block == 0 else S
    kpos = jnp.arange(S)

    @jax.checkpoint
    def rows(qb, qpos):
        s = jnp.einsum("qkgd,ckd->kgqc", qb, k, precision=HIGHEST) \
            / math.sqrt(d)
        seen = kpos[None, :] <= qpos[:, None]
        if window:
            seen &= kpos[None, :] > qpos[:, None] - window
        p = operand(jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1))
        return jnp.einsum("kgqc,ckd->qkgd", p, v, precision=HIGHEST)

    out = lax.map(lambda args: rows(*args),
                  (q.reshape((S // block, block) + q.shape[1:]),
                   kpos.reshape(S // block, block)))
    return out.reshape(q.shape)


def _routed(z, p, prefix, cfg, operand, drop_expert, chunk):
    """What the held experts add: for every token and every held expert,
    the router's weight (nought where the expert is not among the token's
    chosen) times the expert's output. No sort, no gather: every held
    expert runs over every token, a chunk of tokens at a time."""
    E, K = cfg["num_experts"], cfg["num_experts_per_tok"]
    held, off = cfg["num_experts_held"], cfg.get("expert_offset", 0)
    s = jax.nn.sigmoid(jnp.dot(operand(z), operand(
        p[prefix + "router_weight"]).T, precision=HIGHEST))
    top, idx = lax.top_k(s, K)
    w = cfg["moe_routed_scaling_factor"] * top \
        / jnp.sum(top, -1, keepdims=True)
    weight = jnp.zeros((z.shape[0], E), jnp.float32).at[
        jnp.arange(z.shape[0])[:, None], idx].add(w)[:, off:off + held]
    if drop_expert:
        # the planted fault: the held expert most tokens chose adds nothing
        fullest = jnp.argmax(jnp.sum(weight > 0, axis=0))
        weight = jnp.where(jnp.arange(held)[None, :] == fullest, 0.0, weight)
    wg, wu, wd = (operand(p[prefix + f"expert_{n}_weight"])
                  for n in ("gate", "up", "down"))

    @jax.checkpoint
    def experts(zc, wc):
        h = operand(jax.nn.silu(operand(jnp.einsum(
            "td,edf->etf", zc, wg, precision=HIGHEST))) * operand(
            jnp.einsum("td,edf->etf", zc, wu, precision=HIGHEST)))
        return operand(jnp.einsum("etf,efd->td", h * wc.T[:, :, None], wd,
                                  precision=HIGHEST))

    T = z.shape[0]
    chunk = chunk if T % chunk == 0 else T
    out = lax.map(lambda zw: experts(*zw),
                  (operand(z).reshape(T // chunk, chunk, -1),
                   weight.reshape(T // chunk, chunk, -1)))
    return out.reshape(z.shape), idx


def _document_loss(cfg, p, ids, labels, operand, drop_expert):
    """Summed next-token cross-entropy of one document (S,), and the
    experts each token chose in every routed layer."""
    eps, d = cfg["rms_norm_eps"], cfg["head_dim"]
    kv = cfg["num_key_value_heads"]
    ref = cfg["reference"]
    x = operand(p["embed_weight"])[ids.astype(jnp.int32)]
    S = x.shape[0]
    chosen = []
    for k, (kind, heads, mlp) in enumerate(_layers(cfg)):
        pre = f"layer{k}_"
        rope = cfg["rope_parameters"][kind]
        window = cfg["sliding_window"] if kind == "sliding_attention" else 0

        def attention(x, pre=pre, heads=heads, rope=rope, window=window):
            u = _rms(x, p[pre + "attn_norm_gamma"], eps)
            q = _rotate(_mm(u, p[pre + "q_weight"], operand)
                        .reshape(S, heads, d), rope, d)
            key = _rotate(_mm(u, p[pre + "k_weight"], operand)
                          .reshape(S, kv, d), rope, d)
            v = _mm(u, p[pre + "v_weight"], operand).reshape(S, kv, d)
            out = _attend(operand(q).reshape(S, kv, heads // kv, d),
                          operand(key), v, window, operand,
                          ref["query_block"]).reshape(S, heads, d)
            if cfg["gating"]:
                out = out * jax.nn.sigmoid(
                    _mm(u, p[pre + "gate_weight"], operand))[..., None]
            return x + _mm(out.reshape(S, heads * d), p[pre + "o_weight"],
                           operand)

        x = jax.checkpoint(attention)(x)

        def mlp_block(x, pre=pre, mlp=mlp):
            z = _rms(x, p[pre + "mlp_norm_gamma"], eps)
            if mlp == "dense":
                return x + _swiglu(z, p[pre + "mlp_gate_weight"],
                                   p[pre + "mlp_up_weight"],
                                   p[pre + "mlp_down_weight"], operand), None
            routed, idx = _routed(z, p, pre + "moe_", cfg, operand,
                                  drop_expert, ref["token_chunk"])
            shared = _swiglu(z, p[pre + "moe_shared_gate_weight"],
                             p[pre + "moe_shared_up_weight"],
                             p[pre + "moe_shared_down_weight"], operand)
            return x + routed + shared, idx

        x, idx = jax.checkpoint(mlp_block)(x)
        if idx is not None:
            chosen.append(idx)
    x = _rms(x, p["final_norm_gamma"], eps)

    @jax.checkpoint
    def head(block, idx):
        logits = _mm(block, p["lm_head_weight"], operand)
        return -jnp.sum(jnp.take_along_axis(
            jax.nn.log_softmax(logits), idx[:, None], axis=1))

    chunk = ref["token_chunk"] if S % ref["token_chunk"] == 0 else S
    total = jnp.sum(lax.map(
        lambda xl: head(*xl),
        (x.reshape(S // chunk, chunk, -1),
         labels.astype(jnp.int32).reshape(S // chunk, chunk))))
    return total, chosen


def forward_loss(cfg, p, ids, labels, operand, drop_expert=False):
    """Mean next-token cross-entropy over the batch's tokens, float32
    throughout, matmuls at ``highest``; and the chosen experts."""
    totals, chosen = jax.vmap(
        lambda i, l: _document_loss(cfg, p, i, l, operand, drop_expert))(
            ids, labels)
    return jnp.sum(totals) / ids.size, chosen


@functools.lru_cache(maxsize=None)
def _reference_step(cfg_json, precision, drop_expert):
    cfg = json.loads(cfg_json)
    operand = _OPERAND[precision]
    opt = cfg["optimizer"]
    lr, b1, b2 = opt["learning_rate"], opt["beta1"], opt["beta2"]
    eps, wd = opt["epsilon"], opt["wd"]

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(p, mean, var, t, ids, labels):
        (loss, _), g = jax.value_and_grad(
            lambda q: forward_loss(cfg, q, ids, labels, operand,
                                   drop_expert), has_aux=True)(p)
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
    every routed layer, the output of the held expert most tokens chose."""
    step = _reference_step(json.dumps(cfg, sort_keys=True), precision,
                           fault == "expert_out")
    p = init_params(cfg, seed)
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
                p, mean, var, jnp.float32(k + 1), jnp.asarray(x),
                jnp.asarray(y))
        losses.append(float(loss))
        if k == 0:
            grad_norms = {n: float(v)
                          for n, v in jax.device_get(norms).items()}
    delta = {n: float(v) for n, v in jax.device_get(_delta_norms(
        p, seed_key(seed), tuple(param_shapes(cfg)),
        float(cfg["init_std"]))).items()}
    return {"losses": losses, "grad_norms": grad_norms, "delta_norms": delta}


def routing_agreement(cfg, traffic, seed):
    """Per routed layer, the share of the token-choices (tokens x experts
    per token) that the program and the reference make alike at the first
    step: the program's router inputs are what its own forward (compute
    dtype, the chip's kernels) hands the routed layers, the reference's are
    float32. Top-k of near-equal scores may differ; the comparison that
    decides ``correct`` cannot carry this number, so it is read by hand:
    ``PYTHONPATH=. python3 perfbench/models/laguna-xs2.py [seed ...]`` from
    the root of the checkout, on the chip."""
    import mxnet_tpu as mx
    from mxnet_tpu import models
    from mxnet_tpu.executor import build_graph_eval

    params = init_params(cfg, seed)
    ids, labels = (jnp.asarray(a) for a in
                   make_batches(cfg, traffic, seed, 1)[0])
    sparse = [k for k, (_, _, mlp) in enumerate(_layers(cfg))
              if mlp == "sparse"]
    inner = models.get_symbol("decoder_lm", cfg=cfg).get_internals()
    routed_in = mx.sym.Group([inner[f"layer{k}_mlp_norm_output"]
                              for k in sparse])
    forward = build_graph_eval(routed_in)
    dtype = jnp.dtype(cfg["compute_dtype"])

    @jax.jit
    def program_choices(p):
        p = {n: v.astype(dtype) if v.ndim >= 2 else v for n, v in p.items()}
        aux = {n: jnp.zeros(3) for n in routed_in.list_auxiliary_states()}
        outs, _ = forward(dict(p, data=ids, softmax_label=ids), aux, None,
                          False)
        return [lax.top_k(jax.nn.sigmoid(lax.dot_general(
            z.reshape(-1, z.shape[-1]), p[f"layer{k}_moe_router_weight"],
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)),
            cfg["num_experts_per_tok"])[1] for k, z in zip(sparse, outs)]

    with jax.default_matmul_precision("highest"):
        _, reference_choices = jax.jit(lambda p: forward_loss(
            cfg, p, ids, labels, _OPERAND["float32"]))(params)
    out = {}
    for k, mine, theirs in zip(sparse, program_choices(params),
                               reference_choices):
        theirs = theirs.reshape(mine.shape)
        same = (mine[:, :, None] == theirs[:, None, :]).any(-1)
        out[f"layer{k}"] = float(jnp.mean(same))
    return out


if __name__ == "__main__":
    import sys
    from perfbench import run as harness
    cell = harness.load_cell("laguna-xs2.train-fed-seq8k",
                             "--rehearse" in sys.argv)
    for seed in [int(a) for a in sys.argv[1:] if a.isdigit()] or [41]:
        print(json.dumps({"seed": seed, "routing_agreement":
                          routing_agreement(cell["cfg"],
                                            cell["traffic_params"], seed)}),
              flush=True)
