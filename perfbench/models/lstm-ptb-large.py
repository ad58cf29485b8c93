"""The 2-layer LSTM language model for the benchmark: the program under test
built through ``Module`` (its users' entry), weights and batches from the
seed, the operation count, and the plain float32 reference. Widths come from
the configuration, the unrolled length and the rows from the traffic mix.

From the program this file takes the symbol API, ``FusedRNNCell``, ``Module``
and an ``EvalMetric`` (the traffic's ``eval_metric``, or a do-nothing one:
then ``fit`` reads no output back, which a user's metric would); the
reference half imports nothing of it.
The packed ``lstm_parameters`` vector follows the public cuDNN layout: every
layer's ``w_i2h`` (4H x in) then ``w_h2h`` (4H x H), then every layer's
``b_i2h`` and ``b_h2h``; gate order i, f, g, o.
"""
import functools
import json

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from perfbench.seeded import OPERAND, seed_key

HIGHEST = lax.Precision.HIGHEST


def leaf_shapes(cfg):
    """The leaves the comparison reads, name -> shape; the packed vector is
    the concatenation of the ``l<k>_*`` leaves in the cuDNN order."""
    H, E, V = cfg["hidden_size"], cfg["embedding_size"], cfg["vocab_size"]
    shapes = {"embed_weight": (V, E)}
    for k in range(cfg["num_layers"]):
        shapes[f"l{k}_i2h_weight"] = (4 * H, E if k == 0 else H)
        shapes[f"l{k}_h2h_weight"] = (4 * H, H)
    for k in range(cfg["num_layers"]):
        shapes[f"l{k}_i2h_bias"] = (4 * H,)
        shapes[f"l{k}_h2h_bias"] = (4 * H,)
    shapes["pred_weight"] = (V, H)
    shapes["pred_bias"] = (V,)
    return shapes


def init_params(cfg, seed):
    """float32 leaves from the seed in one jitted call: every parameter
    uniform in [-init_scale, init_scale], as the source initialises them."""
    shapes = leaf_shapes(cfg)
    scale = float(cfg["init_scale"])

    @jax.jit
    def make(key):
        return {name: jax.random.uniform(
            jax.random.fold_in(key, k), shape, jnp.float32, -scale, scale)
            for k, (name, shape) in enumerate(shapes.items())}

    return make(seed_key(seed))


def pack(cfg, leaves):
    """The program's arguments from the leaves (host arrays)."""
    rnn = [n for n in leaf_shapes(cfg) if n[0] == "l" and n[1].isdigit()]
    packed = np.concatenate([np.asarray(leaves[n]).ravel() for n in rnn])
    out = {n: np.asarray(v) for n, v in leaves.items() if n not in rnn}
    out["lstm_parameters"] = packed
    return out


def unpack(cfg, args):
    """The leaves from the program's arguments (host arrays)."""
    out, off = {}, 0
    flat = np.asarray(args["lstm_parameters"]).ravel()
    for name, shape in leaf_shapes(cfg).items():
        if name[0] == "l" and name[1].isdigit():
            size = int(np.prod(shape))
            out[name] = flat[off:off + size].reshape(shape)
            off += size
        else:
            out[name] = np.asarray(args[name])
    return out


def make_batches(cfg, traffic, seed, count=None):
    rows = traffic["per_chip_batch"] * traffic["chips"]
    T, V = traffic["seq_len"], cfg["vocab_size"]
    rng = np.random.default_rng([int(seed), 0xDA7A])
    return [(rng.integers(0, V, (rows, T)).astype(np.float32),
             rng.integers(0, V, (rows, T)).astype(np.float32))
            for _ in range(count or traffic["distinct_batches"])]


def items_per_batch(cfg, traffic):
    return traffic["per_chip_batch"] * traffic["chips"] * traffic["seq_len"]


def flops_per_item(cfg):
    """Training FLOPs of one token: 3 x forward; forward is, per layer, the
    input and the recurrent projection (4H x in and 4H x H multiply-adds, 2
    FLOPs each: 16 H^2 at in = H) and the head (2 H V). The embedding is a
    gather."""
    H, E, V = cfg["hidden_size"], cfg["embedding_size"], cfg["vocab_size"]
    fwd = 0
    for k in range(cfg["num_layers"]):
        fwd += 2 * 4 * H * ((E if k == 0 else H) + H)
    return 3 * (fwd + 2 * H * V)


# -- the program under test ----------------------------------------------------

def _host_norms(leaves):
    return {n: float(np.sqrt(np.sum(np.square(v.astype(np.float64)))))
            for n, v in leaves.items()}


@jax.jit
def _token_loss(probs, labels):
    idx = labels.reshape(-1).astype(jnp.int32)
    p = jnp.take_along_axis(probs, idx[:, None], axis=1)[:, 0]
    return -jnp.mean(jnp.log(jnp.maximum(p.astype(jnp.float32), 1e-30)))


class Program:
    """``Module`` bound, initialised from the seed and optimizer-ready: the
    one object the checked steps and the window both drive through
    ``Module.fit``."""

    input_names = ("data", "softmax_label")

    def __init__(self, cfg, traffic, seed, devices):
        import mxnet_tpu as mx
        from mxnet_tpu.io import DataDesc

        if traffic["chips"] != 1:
            raise SystemExit("the LSTM LM cell is a one-chip cell")
        self.cfg, self.traffic, self._seed = cfg, traffic, seed
        self.device = devices[0]
        T, H = traffic["seq_len"], cfg["hidden_size"]
        V, E = cfg["vocab_size"], cfg["embedding_size"]
        N = self.rows = traffic["per_chip_batch"]
        data = mx.sym.var("data")
        embed = mx.sym.Embedding(data, input_dim=V, output_dim=E,
                                 name="embed")
        embed = mx.sym.SwapAxis(embed, dim1=0, dim2=1)      # NTC -> TNC
        stack = mx.rnn.FusedRNNCell(H, num_layers=cfg["num_layers"],
                                    mode="lstm", prefix="lstm_")
        out, _ = stack.unroll(T, inputs=embed, merge_outputs=True,
                              layout="TNC")
        pred = mx.sym.Reshape(out, shape=(-1, H))
        pred = mx.sym.FullyConnected(pred, num_hidden=V, name="pred")
        label = mx.sym.Reshape(mx.sym.var("softmax_label"), shape=(-1,))
        net = mx.sym.SoftmaxOutput(pred, label, name="softmax")
        self.mod = mx.mod.Module(net, data_names=["data"],
                                 label_names=["softmax_label"])
        self.mod.bind(data_shapes=[DataDesc("data", (N, T))],
                      label_shapes=[DataDesc("softmax_label", (N, T))])
        self._start = jax.device_get(init_params(cfg, seed))
        args = {n: mx.nd.array(v) for n, v in pack(cfg, self._start).items()}
        self.mod.init_params(initializer=None, arg_params=args,
                             aux_params={})
        opt = cfg["optimizer"]
        self.lr = float(opt["learning_rate"])
        self.mod.init_optimizer(
            optimizer=opt["name"],
            optimizer_params={"learning_rate": self.lr,
                              "momentum": opt["momentum"], "wd": opt["wd"]})

        class Quiet(mx.metric.EvalMetric):
            """Reads nothing back: the step is timed without a metric's
            read-back of the softmax."""

            def __init__(self):
                super().__init__("quiet")

            def update(self, labels, preds):
                pass

        named = traffic.get("eval_metric")
        self._metric = mx.metric.create(named) if named else Quiet()

    def input_shardings(self):
        from jax.sharding import SingleDeviceSharding
        one = SingleDeviceSharding(self.device)
        return {"data": one, "softmax_label": one}

    def fit(self, feed, on_batch_end=None):
        self.mod.fit(feed, eval_metric=self._metric, num_epoch=1,
                     batch_end_callback=on_batch_end)

    def sync(self):
        stepper = getattr(self.mod, "_fused_stepper", None)
        if stepper not in (None, False):
            jax.block_until_ready(stepper._params)
        else:
            jax.block_until_ready(
                [a._data for a in self.mod._exec.arg_dict.values()])

    def step_loss(self, param, labels):
        probs = self.mod.get_outputs()[0]._data
        return float(_token_loss(probs, jnp.asarray(labels)))

    def _leaves(self):
        args, _ = self.mod.get_params()
        return unpack(self.cfg, {n: v.asnumpy() for n, v in args.items()})

    def grad_norms(self):
        """After the first step of plain SGD the gradient the optimizer got
        is (start - now) / lr, leaf by leaf."""
        now = self._leaves()
        return {n: v / self.lr for n, v in _host_norms(
            {n: now[n] - self._start[n] for n in now}).items()}

    def delta_norms(self):
        now = self._leaves()
        return _host_norms({n: now[n] - self._start[n] for n in now})

    def counters(self):
        stepper = getattr(self.mod, "_fused_stepper", None)
        if stepper in (None, False):
            raise SystemExit("Module.fit did not take the fused step")
        return {"step_programs": int(stepper.guard.count)}

    def close(self):
        self.mod = None


# -- the plain reference ---------------------------------------------------------

def _matmul_t(x, w, operand):
    """x @ w.T at full precision, operands (and result) through the
    control's rounding."""
    return operand(jnp.dot(operand(x), operand(w).T, precision=HIGHEST))


def _lstm_layer(x, w_i2h, w_h2h, b_i2h, b_h2h, operand):
    """x (T, N, in) -> (T, N, H); zero initial state; gates i, f, g, o."""
    T, N, _ = x.shape
    H = w_h2h.shape[1]
    xproj = _matmul_t(x.reshape(T * N, -1), w_i2h, operand) \
        .reshape(T, N, 4 * H) + b_i2h + b_h2h

    @jax.checkpoint     # keep only (h, c) per time step; gates are recomputed
    def step(carry, xp):
        h, c = carry
        g = xp + _matmul_t(h, w_h2h, operand)
        i, f = jax.nn.sigmoid(g[:, :H]), jax.nn.sigmoid(g[:, H:2 * H])
        u, o = jnp.tanh(g[:, 2 * H:3 * H]), jax.nn.sigmoid(g[:, 3 * H:])
        c = f * c + i * u
        h = operand(o * jnp.tanh(c))
        return (h, c), h

    zero = jnp.zeros((N, H), jnp.float32)
    _, out = lax.scan(step, (zero, zero), xproj)
    return out


def forward_loss(cfg, p, ids, labels, operand, chunks=8):
    """Sum over tokens of the cross-entropy, divided by the rows: what the
    program's SoftmaxOutput gradient and rescale_grad = 1/rows amount to.
    Also returns the mean token cross-entropy. The head runs in
    rematerialised chunks of time steps so that the logits never stand
    whole."""
    N, T = ids.shape
    x = jnp.swapaxes(operand(p["embed_weight"])[ids.astype(jnp.int32)], 0, 1)
    for k in range(cfg["num_layers"]):
        x = _lstm_layer(x, p[f"l{k}_i2h_weight"], p[f"l{k}_h2h_weight"],
                        p[f"l{k}_i2h_bias"], p[f"l{k}_h2h_bias"], operand)
    H = x.shape[-1]
    rows = x.reshape(T * N, H)                    # (T, N) order
    flat = labels.reshape(-1).astype(jnp.int32)   # (N, T) order, as tracked
    chunks = chunks if (T * N) % chunks == 0 else 1

    @jax.checkpoint
    def head(block, idx):
        logits = _matmul_t(block, p["pred_weight"], operand) + p["pred_bias"]
        logp = jax.nn.log_softmax(logits)
        return -jnp.sum(jnp.take_along_axis(logp, idx[:, None], axis=1))

    total = 0.0
    for block, idx in zip(jnp.split(rows, chunks), jnp.split(flat, chunks)):
        total = total + head(block, idx)
    return total / N, total / (T * N)


@functools.lru_cache(maxsize=None)
def _reference_step(cfg_json, precision):
    cfg = json.loads(cfg_json)
    operand = OPERAND[precision]
    lr = float(cfg["optimizer"]["learning_rate"])
    if cfg["optimizer"]["momentum"] or cfg["optimizer"]["wd"]:
        raise SystemExit("the LM reference is plain SGD")

    @jax.jit
    def step(p, ids, labels):
        (_, mean), g = jax.value_and_grad(
            lambda q: forward_loss(cfg, q, ids, labels, operand),
            has_aux=True)(p)
        norms = {n: jnp.sqrt(jnp.sum(jnp.square(v))) for n, v in g.items()}
        return mean, {n: p[n] - lr * g[n] for n in p}, norms

    return step


def reference(cfg, traffic, seed, precision="float32", fault=None,
              devices=None):
    """The first ``check_steps`` steps from the seed in plain jnp."""
    step = _reference_step(json.dumps(cfg, sort_keys=True), precision)
    start = init_params(cfg, seed)
    p = start
    losses, grad_norms = [], None
    batches = make_batches(cfg, traffic, seed, traffic["check_steps"])
    for k, (x, y) in enumerate(batches):
        if fault == "half_batch":
            x, y = x[:len(x) // 2], y[:len(y) // 2]
        with jax.default_matmul_precision("highest"):
            loss, p, norms = step(p, jnp.asarray(x), jnp.asarray(y))
        losses.append(float(loss))
        if k == 0:
            grad_norms = {n: float(v)
                          for n, v in jax.device_get(norms).items()}
    delta = {n: float(jnp.sqrt(jnp.sum(jnp.square(p[n] - start[n]))))
             for n in p}
    return {"losses": losses, "grad_norms": grad_norms, "delta_norms": delta}
