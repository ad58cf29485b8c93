"""SDAR-30B-A3B-Chat (JetLM, 30B-A3B, ``model_type: sdar_moe``) for the
benchmark, as one chip of an eight-chip expert-parallel deployment trains it
under the block-diffusion objective: the program under test built through
its users' entry, weights and batches (the noise among them) from the seed,
the operation count, and the plain float32 reference.

From the program this file takes ``models.get_symbol("decoder_lm",
cfg=...)``, ``SPMDTrainer`` with ``mx.optimizer.Adam`` and ``make_mesh`` and
nothing else; the reference half (``reference`` and the functions under
it) imports nothing of the program and takes nothing it made: weights and
batches are regenerated from the seed, and the layer equations are written
out again here, in ``jax.numpy``, with no kernel and no sort.

The objective (BD3-LM, arXiv:2503.09573, as SDAR, arXiv:2510.06303, trains
with it). A document x_0 of L tokens in blocks of B: block b draws t_b
uniform on [t_min, t_max]; each of its tokens is replaced by the mask token
independently with probability t_b, giving x_t; a masked position carries
the weight 1 / t_b, every other 0. One step runs ``[x_t ; x_0]``, 2 L rows,
both halves at rotary positions 0 .. L - 1, under the mask

    blk(i) = (i mod L) // B;  noisy(i) = i < L
    see(q, k) =  noisy(q) and  noisy(k) and blk(q) == blk(k)
              or noisy(q) and !noisy(k) and blk(q) >  blk(k)
              or !noisy(q) and !noisy(k) and blk(q) >= blk(k)

and the loss is sum_i w_i CE(logits_i, x0_i) / (rows L) over the noisy half,
position i predicting token i (no shift).

The layer equations, as the ``sdar_moe`` / Qwen3-MoE modelling code has them
(x is 2L x D per document, D = 2048; no bias anywhere; RMSNorm with a learned
gain, eps 1e-6). Every layer: x = x + attention(RMSNorm(x)); x = x +
routed(RMSNorm(x)); after the last layer RMSNorm and the head over the first
L rows.

* attention: q (32 x 128), k and v (4 x 128); q and k each RMS-normalised
  over the 128 dims of a head with a learned gain (128,) BEFORE the rotary
  embedding; rotary over all 128 dims, theta 1e6, dimension i paired with
  i + 64, position (row mod L); softmax of q k^T / sqrt(128) under the mask
  above, query head h reading key/value head h // 8; o projection.
* routed layer, z = RMSNorm(x): p = softmax(z W_r^T) over all 128 experts in
  float32; the 8 largest are chosen, their weights p_e / (sum of the chosen
  p); each expert W_2 (silu(W_1 z) * W_3 z), width 768; the sum runs over
  the chosen experts HELD HERE (0-15 of the 128). No shared expert, no bias.
* head: its own matrix (untied), over the held rows.

Departures, each also under ``assumed`` in the configuration's file: the
block length, the noise schedule and the mask token's row are set here (the
catalog gives none); q/k norm and the softmax router are the modelling
code's (the config has no key for either); what the 112 absent experts would
add is left out, here and in the program alike; the last layer's clean half
feeds nothing and is computed all the same (its work is counted in
``flops_per_item``: pruning it would gain here, at 4 layers, what no
deployment of 48 sees). Adam as ``mx.optimizer.Adam`` runs it: lr_t = lr
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
    """The published configuration: the cut keys at their published
    values, the mask token the last row of the uncut vocabulary as it is
    the last held row here (assumed: the ``config.json`` names none)."""
    whole = dict(cfg, **cfg["published"])
    return dict(whole, mask_token_id=whole["vocab_size"] - 1)


def param_shapes(cfg):
    """name -> shape, under the names and in the layouts of the program's
    arguments: ``FullyConnected`` weights (out, in), the held experts'
    stacked (Eh, in, out)."""
    D, d = cfg["hidden_size"], cfg["head_dim"]
    H, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    f, held = cfg["moe_intermediate_size"], cfg["num_experts_held"]
    shapes = {"embed_weight": (cfg["vocab_size"], D)}
    for k in range(cfg["num_hidden_layers"]):
        p = f"layer{k}_"
        shapes[p + "attn_norm_gamma"] = (D,)
        shapes[p + "q_weight"] = (H * d, D)
        shapes[p + "k_weight"] = (kv * d, D)
        shapes[p + "q_norm_gamma"] = (d,)
        shapes[p + "k_norm_gamma"] = (d,)
        shapes[p + "v_weight"] = (kv * d, D)
        shapes[p + "o_weight"] = (D, H * d)
        shapes[p + "mlp_norm_gamma"] = (D,)
        shapes[p + "moe_router_weight"] = (cfg["num_experts"], D)
        shapes[p + "moe_expert_gate_weight"] = (held, D, f)
        shapes[p + "moe_expert_up_weight"] = (held, D, f)
        shapes[p + "moe_expert_down_weight"] = (held, f, D)
    shapes["final_norm_gamma"] = (D,)
    shapes["lm_head_weight"] = (cfg["vocab_size"], D)
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
    """The cell's distinct host batches, the noise part of them. A row is
    one document x_0 of L tokens, its ids Zipf-distributed over the held
    rows of the vocabulary but the mask token's (p(id r - 1) ~
    r^-exponent); block b of it draws t_b uniform on [t_min, t_max] and each
    of its tokens a coin of probability t_b. ``data`` (rows, 2 L) is ``[x_t ;
    x_0]``, x_t the document with the coins' tokens replaced by
    ``mask_token_id``; ``label`` (rows, 2, L) float32 holds x_0 in plane 0
    and in plane 1 the weight 1 / t_b where the token is masked, 0
    elsewhere."""
    rows = traffic["per_chip_batch"] * traffic["chips"]
    L, B = traffic["seq_len"], cfg["block_length"]
    if traffic.get("block_length", B) != B:
        raise SystemExit(
            f"the traffic's block_length {traffic['block_length']} is not "
            f"the configuration's {B}, which the program's mask is built on")
    mask_id = cfg["mask_token_id"]
    if mask_id != cfg["vocab_size"] - 1:
        raise SystemExit("the mask token is the last held row of the "
                         "vocabulary: the documents' ids are drawn under it")
    p = np.arange(1, mask_id + 1, dtype=np.float64) ** -float(
        traffic["zipf_exponent"])
    p /= p.sum()
    t_min, t_max = cfg["noise"]["t_min"], cfg["noise"]["t_max"]
    rng = np.random.default_rng([int(seed), 0xDA7A])
    out = []
    for _ in range(count or traffic["distinct_batches"]):
        x0 = rng.choice(mask_id, size=(rows, L), p=p)
        t = np.repeat(rng.uniform(t_min, t_max, size=(rows, L // B)), B,
                      axis=1)
        masked = rng.random(size=(rows, L)) < t
        xt = np.where(masked, mask_id, x0)
        weight = np.where(masked, 1.0 / t, 0.0)
        out.append((np.concatenate([xt, x0], 1).astype(np.float32),
                    np.stack([x0, weight], 1).astype(np.float32)))
    return out


def items_per_batch(cfg, traffic):
    """Tokens of data a step: a document counts once, though the step runs
    a noisy and a clean row for each of its tokens."""
    return traffic["per_chip_batch"] * traffic["chips"] * traffic["seq_len"]


def flops_per_item(cfg):
    """Training FLOPs of one token of data at ``flops_seq_len`` tokens a
    document: 3 x forward, 2 per multiply-add, nothing recomputed counted.
    A token is two rows through the layers (the noisy and the clean copy:
    the q, k, v and o projections, the router over all experts and the
    routed experts at the even load, top_k x held / experts of them a row),
    L + B query-key pairs a head, scores and values (the mask's L (L + B)
    live pairs over L tokens, whatever tiles a kernel visits), and one row
    through the head over the held rows of the vocabulary. The last layer's
    clean half is counted like every other layer's. Norms, rotary, softmax
    and the gather of the embedding are left out."""
    L, B = cfg["flops_seq_len"], cfg["block_length"]
    D, d = cfg["hidden_size"], cfg["head_dim"]
    H, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    routed = cfg["num_experts_per_tok"] * cfg["num_experts_held"] \
        / cfg["num_experts"]
    row = 2 * D * H * d + 2 * D * kv * d + D * cfg["num_experts"] \
        + routed * 3 * D * cfg["moe_intermediate_size"]
    layer = 2 * row + 2 * H * d * (L + B)
    macs = cfg["num_hidden_layers"] * layer + D * cfg["vocab_size"]
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


# the counters the graph's ops keep on the device: the routed layers', as
# ``MoEFFN`` declares them, and the weighted loss's
DEVICE_COUNTERS = ("moe.assignments_held", "moe.load_max", "moe.overflow",
                   "loss.weighted_tokens")


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
        self._profiler, self._counted = mx.profiler, None
        chips = traffic["chips"]
        self.mesh = make_mesh(dict(traffic["mesh"]),
                              devices=list(devices)[:chips])
        rows, S = traffic["per_chip_batch"] * chips, traffic["seq_len"]
        self.sym = models.get_symbol("decoder_lm", cfg=cfg)
        # a program that does not know the objective builds its next-token
        # graph from the keys it knows: refused here, by name
        if "loss_stats" not in self.sym.list_auxiliary_states():
            raise SystemExit(
                f"decoder_lm built no weighted loss for the objective "
                f"{cfg['objective']!r}: this program cannot express the "
                f"configuration")
        opt = cfg["optimizer"]
        self.beta1 = float(opt["beta1"])
        self.trainer = SPMDTrainer(
            self.sym, optimizer=mx.optimizer.Adam(
                learning_rate=float(opt["learning_rate"]), beta1=self.beta1,
                beta2=float(opt["beta2"]), epsilon=float(opt["epsilon"]),
                wd=float(opt["wd"])),
            mesh=self.mesh, compute_dtype=cfg["compute_dtype"])
        host = jax.device_get(init_params(cfg, seed))
        shapes = {"data": (rows, 2 * S), "softmax_label": (rows, 2, S)}
        _, _, aux_shapes = self.sym.infer_shape(**shapes)
        aux = {n: np.zeros(shape, np.float32) for n, shape in zip(
            self.sym.list_auxiliary_states(), aux_shapes)}
        self.trainer.bind(
            data_shapes={"data": shapes["data"]},
            label_shapes={"softmax_label": shapes["softmax_label"]},
            arg_params=host, aux_params=aux)

    def input_shardings(self):
        from jax.sharding import NamedSharding, PartitionSpec as P
        return {n: NamedSharding(self.mesh, P("data"))
                for n in self.input_names}

    def fit(self, feed, on_batch_end=None):
        self.trainer.fit(feed, num_epoch=1, batch_end_callback=on_batch_end)

    def sync(self):
        jax.block_until_ready(self.trainer.params)

    def step_loss(self, param, labels):
        """The step's own output: the weighted cross-entropy of the noisy
        half over rows x L."""
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

    def device_counters(self):
        """The counters the graph's ops keep on the device since bind, each
        summed over the nodes that keep it: one boundary read
        (``SPMDTrainer.aux_counters``)."""
        nodes = self.trainer.aux_counters().values()
        return {k: sum(node.get(k, 0) for node in nodes)
                for k in DEVICE_COUNTERS}

    def counters(self):
        """The step programs compiled, and the layers whose attention
        lowered to the kernel with the block-diffusion schedule (the
        program's counter ``attention.block_diffusion_layers``, counted
        while a step is traced: it moves in set-up alone). The driver calls
        this at the window's two ends and nowhere else, so each call is
        also the boundary read of the device's counters: what
        ``moe.*`` and ``loss.weighted_tokens`` grew by since the call
        before is added to the program's counters of the same names
        (``mx.profiler.count``), where the readers find the window's share
        beside ``step.count``."""
        now = self.device_counters()
        for k in DEVICE_COUNTERS if self._counted is not None else ():
            self._profiler.count(k, int(now[k] - self._counted[k]))
        self._counted = now
        return {"step_programs": int(self.trainer.retrace_guard.count),
                "block_diffusion_layers": int(self._profiler.counters().get(
                    "attention.block_diffusion_layers", 0))}

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
FAULTS = ("leak", "blind", "half_batch")


def _mm(x, w, operand):
    """x @ w.T at full precision, operands and result through the control's
    rounding."""
    return operand(jnp.dot(operand(x), operand(w).T, precision=HIGHEST))


def _rms(x, gain, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * gain


def _rotate(x, theta, length):
    """x (2L, heads, d): every head rotated by its row's position, row mod
    ``length``, over all d dims, dim i paired with i + d / 2, frequencies
    theta^(-2i/d)."""
    d = x.shape[-1]
    inv = theta ** (-2.0 * jnp.arange(d // 2, dtype=jnp.float32) / d)
    position = (jnp.arange(x.shape[0]) % length).astype(jnp.float32)
    angle = position[:, None] * inv[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def see(q, k, length, block, fault=None):
    """The block-diffusion mask over ``[noisy ; clean]`` rows of ``length``
    each: is key row ``k`` seen from query row ``q``? Two planted faults
    change the offset block-causal part: ``leak`` makes it ``>=``, so a
    noisy block sees its own clean tokens too (``block`` keys more a row);
    ``blind`` leaves it out, so a noisy block sees no clean token at all."""
    def blk(i):
        return (i % length) // block

    def noisy(i):
        return i < length

    before = blk(q) >= blk(k) if fault == "leak" else blk(q) > blk(k)
    if fault == "blind":
        before = before & False
    return (noisy(q) & noisy(k) & (blk(q) == blk(k))) \
        | (noisy(q) & ~noisy(k) & before) \
        | (~noisy(q) & ~noisy(k) & (blk(q) >= blk(k)))


def _attend(q, k, v, length, block_length, operand, rows_a_time, fault):
    """q (2L, kv, G, d), k and v (2L, kv, d): softmax attention under the
    explicit mask, a block of query rows at a time against all the keys,
    each block rematerialised."""
    S, d = q.shape[0], q.shape[-1]
    step = rows_a_time if S % rows_a_time == 0 else S
    kpos = jnp.arange(S)

    @jax.checkpoint
    def rows(qb, qpos):
        s = jnp.einsum("qkgd,ckd->kgqc", qb, k, precision=HIGHEST) \
            / math.sqrt(d)
        seen = see(qpos[:, None], kpos[None, :], length, block_length, fault)
        p = operand(jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1))
        return jnp.einsum("kgqc,ckd->qkgd", p, v, precision=HIGHEST)

    out = lax.map(lambda args: rows(*args),
                  (q.reshape((S // step, step) + q.shape[1:]),
                   kpos.reshape(S // step, step)))
    return out.reshape(q.shape)


def _routed(z, p, prefix, cfg, operand, chunk):
    """What the held experts add: for every row and every held expert, the
    router's weight (nought where the expert is not among the row's chosen)
    times the expert's output. No sort, no gather: a loop over the held
    experts, each over every row, a chunk of rows at a time."""
    E, K = cfg["num_experts"], cfg["num_experts_per_tok"]
    held, off = cfg["num_experts_held"], cfg.get("expert_offset", 0)
    s = jax.nn.softmax(jnp.dot(operand(z), operand(
        p[prefix + "router_weight"]).T, precision=HIGHEST), axis=-1)
    top, idx = lax.top_k(s, K)
    w = top / jnp.sum(top, -1, keepdims=True)
    weight = jnp.zeros((z.shape[0], E), jnp.float32).at[
        jnp.arange(z.shape[0])[:, None], idx].add(w)[:, off:off + held]
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


def _document_loss(cfg, p, ids, targets, weights, operand, fault):
    """Summed weighted cross-entropy of one document: ``ids`` (2L,) the
    noisy copy and then the clean one, ``targets`` and ``weights`` (L,)."""
    eps, d = cfg["rms_norm_eps"], cfg["head_dim"]
    H, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    ref = cfg["reference"]
    x = operand(p["embed_weight"])[ids.astype(jnp.int32)]
    S, L = x.shape[0], targets.shape[0]
    for k in range(cfg["num_hidden_layers"]):
        pre = f"layer{k}_"

        def attention(x, pre=pre):
            u = _rms(x, p[pre + "attn_norm_gamma"], eps)
            q = _mm(u, p[pre + "q_weight"], operand).reshape(S, H, d)
            key = _mm(u, p[pre + "k_weight"], operand).reshape(S, kv, d)
            q = _rotate(_rms(q, p[pre + "q_norm_gamma"], eps),
                        cfg["rope_theta"], L)
            key = _rotate(_rms(key, p[pre + "k_norm_gamma"], eps),
                          cfg["rope_theta"], L)
            v = _mm(u, p[pre + "v_weight"], operand).reshape(S, kv, d)
            out = _attend(operand(q).reshape(S, kv, H // kv, d),
                          operand(key), v, L, cfg["block_length"], operand,
                          ref["query_block"], fault)
            return x + _mm(out.reshape(S, H * d), p[pre + "o_weight"],
                           operand)

        x = jax.checkpoint(attention)(x)

        def mlp_block(x, pre=pre):
            z = _rms(x, p[pre + "mlp_norm_gamma"], eps)
            return x + _routed(z, p, pre + "moe_", cfg, operand,
                               ref["token_chunk"])

        x = jax.checkpoint(mlp_block)(x)
    # the noisy half alone is scored
    x = _rms(x[:L], p["final_norm_gamma"], eps)

    @jax.checkpoint
    def head(block, idx, w):
        logits = _mm(block, p["lm_head_weight"], operand)
        return -jnp.sum(w * jnp.take_along_axis(
            jax.nn.log_softmax(logits), idx[:, None], axis=1)[:, 0])

    chunk = ref["token_chunk"] if L % ref["token_chunk"] == 0 else L
    return jnp.sum(lax.map(
        lambda xlw: head(*xlw),
        (x.reshape(L // chunk, chunk, -1),
         targets.astype(jnp.int32).reshape(L // chunk, chunk),
         weights.reshape(L // chunk, chunk))))


def forward_loss(cfg, p, data, label, operand, fault=None):
    """``sum_i w_i CE(logits_i, x0_i) / (rows L)`` over the batch's noisy
    halves, float32 throughout, matmuls at ``highest``; one document after
    another, so the memory is one document's. ``data`` (rows, 2 L) and
    ``label`` (rows, 2, L) as :func:`make_batches` lays them out. The
    planted fault ``half_batch`` gives the blocks of each document's second
    half the weight 0, the normaliser unchanged."""
    targets, weights = label[:, 0], label[:, 1]
    if fault == "half_batch":
        L = weights.shape[1]
        weights = jnp.where(jnp.arange(L)[None, :] < L // 2, weights, 0.0)
    totals = lax.map(lambda dtw: _document_loss(cfg, p, *dtw, operand, fault),
                     (data, targets, weights))
    return jnp.sum(totals) / targets.size


@functools.lru_cache(maxsize=None)
def _reference_step(cfg_json, precision, fault):
    cfg = json.loads(cfg_json)
    operand = _OPERAND[precision]
    opt = cfg["optimizer"]
    lr, b1, b2 = opt["learning_rate"], opt["beta1"], opt["beta2"]
    eps, wd = opt["epsilon"], opt["wd"]

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(p, mean, var, t, data, label):
        loss, g = jax.value_and_grad(
            lambda q: forward_loss(cfg, q, data, label, operand, fault))(p)
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
    the held experts, the held rows of the vocabulary, and the batches with
    their noise. ``precision`` other than float32 is the control.
    ``fault='leak'`` makes the offset block-causal part of the mask ``>=``,
    so a noisy block sees its own clean tokens; ``fault='blind'`` leaves
    that part out, so a noisy block sees no clean token;
    ``fault='half_batch'`` gives half of each document's blocks the weight
    0."""
    if fault not in (None,) + FAULTS:
        raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")
    step = _reference_step(json.dumps(cfg, sort_keys=True), precision, fault)
    p = init_params(cfg, seed)
    mean = jax.tree_util.tree_map(jnp.zeros_like, p)
    var = jax.tree_util.tree_map(jnp.zeros_like, p)
    losses, grad_norms = [], None
    batches = make_batches(cfg, traffic, seed, traffic["check_steps"])
    for k, (x, y) in enumerate(batches):
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
