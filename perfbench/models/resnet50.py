"""ResNet-50 (pre-activation) for the benchmark: the program under test built
through its users' entry, the weights and batches made from the seed, the
operation count, and the plain float32 reference.

Everything the yardstick needs is here or in ``perfbench/``; from the program
this file takes ``models.get_symbol``, ``SPMDTrainer`` and ``make_mesh`` (the
system under test) and nothing else. The reference half (``reference`` and the
functions under it) imports nothing of the program and takes nothing the
program made: weights and batches are regenerated from the seed.
"""
import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from perfbench.seeded import OPERAND, seed_key

HIGHEST = lax.Precision.HIGHEST


# -- sizes and names (the upstream naming convention, not an import) ----------

def _bottleneck(cfg):
    return cfg["num_layers"] >= 50


def param_shapes(cfg):
    """name -> shape, in the order of the symbol's arguments. Convolution
    weights are (out, kh, kw, in): the program's NHWC layout."""
    f = cfg["filters"]
    cin = cfg["image_shape"][2]
    shapes = {"conv0_weight": (f[0], 7, 7, cin),
              "bn0_gamma": (f[0],), "bn0_beta": (f[0],)}
    width = f[0]
    for i, n in enumerate(cfg["units"]):
        out = f[i + 1]
        for j in range(n):
            p = f"stage{i + 1}_unit{j + 1}_"
            shapes[p + "bn1_gamma"] = shapes[p + "bn1_beta"] = (width,)
            if _bottleneck(cfg):
                mid = out // 4
                shapes[p + "conv1_weight"] = (mid, 1, 1, width)
                shapes[p + "bn2_gamma"] = shapes[p + "bn2_beta"] = (mid,)
                shapes[p + "conv2_weight"] = (mid, 3, 3, mid)
                shapes[p + "bn3_gamma"] = shapes[p + "bn3_beta"] = (mid,)
                shapes[p + "conv3_weight"] = (out, 1, 1, mid)
            else:
                shapes[p + "conv1_weight"] = (out, 3, 3, width)
                shapes[p + "bn2_gamma"] = shapes[p + "bn2_beta"] = (out,)
                shapes[p + "conv2_weight"] = (out, 3, 3, out)
            if j == 0:
                shapes[p + "sc_weight"] = (out, 1, 1, width)
            width = out
    shapes["bn1_gamma"] = shapes["bn1_beta"] = (width,)
    shapes["fc1_weight"] = (cfg["num_classes"], width)
    shapes["fc1_bias"] = (cfg["num_classes"],)
    return shapes


def init_params(cfg, seed):
    """float32 master weights from the seed, in one jitted call on the
    default device: He-normal convolutions, N(0, 0.01) classifier, gamma 1,
    beta and bias 0."""
    shapes = param_shapes(cfg)

    @jax.jit
    def make(key):
        out = {}
        for k, (name, shape) in enumerate(shapes.items()):
            if name.endswith("gamma"):
                out[name] = jnp.ones(shape, jnp.float32)
            elif name.endswith(("beta", "bias")):
                out[name] = jnp.zeros(shape, jnp.float32)
            else:
                std = 0.01 if name == "fc1_weight" else \
                    (2.0 / (shape[1] * shape[2] * shape[3])) ** 0.5
                out[name] = std * jax.random.normal(
                    jax.random.fold_in(key, k), shape, jnp.float32)
        return out

    return make(seed_key(seed))


def make_batches(cfg, traffic, seed, count=None):
    """The cell's distinct host batches: one ``Generator(seed)`` block of
    uniform float32 pixels and one of labels per batch, rows all different."""
    rows = traffic["per_chip_batch"] * traffic["chips"]
    h, w, c = cfg["image_shape"]
    rng = np.random.default_rng([int(seed), 0xDA7A])
    return [(rng.random((rows, h, w, c), dtype=np.float32),
             rng.integers(0, cfg["num_classes"], (rows,)).astype(np.float32))
            for _ in range(count or traffic["distinct_batches"])]


def items_per_batch(cfg, traffic):
    return traffic["per_chip_batch"] * traffic["chips"]


def flops_per_item(cfg):
    """Training FLOPs of one image: 3 x (forward), forward = 2 per
    multiply-add over every convolution and the classifier, from the
    shapes. Nothing recomputed is counted; BN, ReLU and pooling are left
    out, as in the published count."""
    side = cfg["image_shape"][0]
    shapes = param_shapes(cfg)
    macs = 0
    side = -(-side // 2)                     # conv0, stride 2
    macs += side * side * int(np.prod(shapes["conv0_weight"]))
    side = -(-side // 2)                     # max pool, stride 2
    for i, n in enumerate(cfg["units"]):
        for j in range(n):
            p = f"stage{i + 1}_unit{j + 1}_"
            down = i > 0 and j == 0
            out_side = -(-side // 2) if down else side
            # the stride sits on the 3x3 (conv2 of a bottleneck, conv1 of a
            # basic unit); what precedes it runs at the input's side
            for name in ("conv1", "conv2", "conv3", "sc"):
                key = p + name + "_weight"
                if key not in shapes:
                    continue
                before = _bottleneck(cfg) and name == "conv1"
                s = side if before else out_side
                macs += s * s * int(np.prod(shapes[key]))
            side = out_side
    macs += int(np.prod(shapes["fc1_weight"]))
    return 3 * 2 * macs


# -- the program under test ----------------------------------------------------

class Program:
    """The compiled step with its state, built once and handed to both the
    checked first steps and the timed window."""

    input_names = ("data", "softmax_label")

    def __init__(self, cfg, traffic, seed, devices):
        import mxnet_tpu as mx  # noqa: F401  (places the compile caches)
        from mxnet_tpu import models
        from mxnet_tpu.parallel import SPMDTrainer, make_mesh

        self.cfg, self.traffic = cfg, traffic
        chips = traffic["chips"]
        self.devices = list(devices)[:chips]
        self.mesh = make_mesh(dict(traffic["mesh"]), devices=self.devices)
        self.rows = items_per_batch(cfg, traffic)
        h, w, c = cfg["image_shape"]
        sym = models.get_symbol(
            "resnet", num_layers=cfg["num_layers"],
            num_classes=cfg["num_classes"], image_shape=f"{h},{w},{c}",
            dtype=cfg["compute_dtype"])
        opt = cfg["optimizer"]
        self.lr = float(opt["learning_rate"])
        self.trainer = SPMDTrainer(
            sym, optimizer=opt["name"],
            optimizer_params=dict(learning_rate=self.lr,
                                  momentum=opt["momentum"], wd=opt["wd"],
                                  rescale_grad=1.0 / self.rows),
            mesh=self.mesh, compute_dtype=cfg["compute_dtype"])
        host = jax.device_get(init_params(cfg, seed))
        aux = {}
        for name in sym.list_auxiliary_states():
            width = host[name.replace("moving_mean", "gamma")
                         .replace("moving_var", "gamma")].shape
            aux[name] = (np.ones if name.endswith("var") else np.zeros)(
                width, np.float32)
        self.trainer.bind(
            data_shapes={"data": (self.rows, h, w, c)},
            label_shapes={"softmax_label": (self.rows,)},
            arg_params=host, aux_params=aux)
        self._seed = seed
        self._labels = None

    # what the feed needs to place resident batches where the step reads them
    def input_shardings(self):
        from jax.sharding import NamedSharding, PartitionSpec as P
        return {"data": NamedSharding(self.mesh, P("data")),
                "softmax_label": NamedSharding(self.mesh, P("data"))}

    def fit(self, feed, on_batch_end=None):
        self.trainer.fit(feed, num_epoch=1, batch_end_callback=on_batch_end)

    def sync(self):
        jax.block_until_ready(self.trainer.params)

    def step_loss(self, param, labels):
        """Cross-entropy of the step that just ran, from the softmax the
        program returned and the batch's labels."""
        probs = np.asarray(param.locals["step_outs"][0], np.float32)
        idx = np.asarray(labels).astype(np.int64)
        return float(-np.log(np.maximum(
            probs[np.arange(idx.size), idx], 1e-30)).mean())

    def grad_norms(self):
        """Per-leaf norm of the gradient the optimizer got, from the
        momentum buffers after the first step: mom = -lr * grad."""
        norms = _leaf_norms(self.trainer.states)
        return {n: float(v) / self.lr for n, v in jax.device_get(norms).items()}

    def delta_norms(self):
        """Per-leaf norm of (parameters now - parameters at the seed)."""
        start = init_params(self.cfg, self._seed)
        repl = jax.sharding.NamedSharding(
            self.mesh, jax.sharding.PartitionSpec())
        start = jax.device_put(start, repl)
        return {n: float(v) for n, v in jax.device_get(
            _delta_norms(self.trainer.params, start)).items()}

    def counters(self):
        return {"step_programs": int(self.trainer.retrace_guard.count)}

    def close(self):
        self.trainer = None


@jax.jit
def _leaf_norms(tree):
    return {n: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for n, v in tree.items()}


@jax.jit
def _delta_norms(now, start):
    return {n: jnp.sqrt(jnp.sum(jnp.square(now[n] - start[n])))
            for n in now}


# -- the plain reference ---------------------------------------------------------

def _conv(x, w, stride, pad, operand):
    # the control keeps its activations in the low type too, as the program
    # keeps its own in bfloat16: operands and result are rounded
    return operand(lax.conv_general_dilated(
        operand(x), operand(w), (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "OHWI", "NHWC"), precision=HIGHEST))


def _bn_relu(x, p, name, eps, operand):
    mean = jnp.mean(x, (0, 1, 2))
    var = jnp.var(x, (0, 1, 2))
    y = (x - mean) * lax.rsqrt(var + eps) * p[name + "_gamma"] \
        + p[name + "_beta"]
    return operand(jnp.maximum(y, 0.0))


def _unit(x, p, name, stride, dim_match, bottleneck, eps, operand):
    act1 = _bn_relu(x, p, name + "bn1", eps, operand)
    if bottleneck:
        y = _conv(act1, p[name + "conv1_weight"], 1, 0, operand)
        y = _bn_relu(y, p, name + "bn2", eps, operand)
        y = _conv(y, p[name + "conv2_weight"], stride, 1, operand)
        y = _bn_relu(y, p, name + "bn3", eps, operand)
        y = _conv(y, p[name + "conv3_weight"], 1, 0, operand)
    else:
        y = _conv(act1, p[name + "conv1_weight"], stride, 1, operand)
        y = _bn_relu(y, p, name + "bn2", eps, operand)
        y = _conv(y, p[name + "conv2_weight"], 1, 1, operand)
    short = x if dim_match else \
        _conv(act1, p[name + "sc_weight"], stride, 0, operand)
    return y + short


def forward_loss(cfg, p, x, y, operand):
    """Mean cross-entropy of pre-activation ResNet on one batch, float32
    throughout, matmuls at ``highest``. Each unit is rematerialised so that
    the timed batch fits beside nothing else on one chip."""
    eps = cfg["bn_eps"]
    h = _conv(x, p["conv0_weight"], 2, 3, operand)
    h = _bn_relu(h, p, "bn0", eps, operand)
    h = lax.reduce_window(h, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          [(0, 0), (1, 1), (1, 1), (0, 0)])
    for i, n in enumerate(cfg["units"]):
        for j in range(n):
            unit = functools.partial(
                _unit, name=f"stage{i + 1}_unit{j + 1}_",
                stride=2 if (i > 0 and j == 0) else 1, dim_match=j > 0,
                bottleneck=_bottleneck(cfg), eps=eps, operand=operand)
            h = jax.checkpoint(unit)(h, p)
    h = _bn_relu(h, p, "bn1", eps, operand)
    h = jnp.mean(h, (1, 2))
    logits = jnp.dot(operand(h), operand(p["fc1_weight"]).T,
                     precision=HIGHEST) + p["fc1_bias"]
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.take_along_axis(
        logp, y.astype(jnp.int32)[:, None], axis=1))


@functools.lru_cache(maxsize=None)
def _reference_step(cfg_json, precision):
    """One jitted reference step per configuration and precision."""
    import json
    cfg = json.loads(cfg_json)
    operand = OPERAND[precision]
    opt = cfg["optimizer"]
    lr, momentum = float(opt["learning_rate"]), float(opt["momentum"])

    @jax.jit
    def step(p, mom, x, y):
        loss, g = jax.value_and_grad(
            lambda q: forward_loss(cfg, q, x, y, operand))(p)
        mom = {n: momentum * mom[n] - lr * (g[n] + opt["wd"] * p[n])
               for n in p}
        return loss, {n: p[n] + mom[n] for n in p}, mom, _leaf_norms(g)

    return step


def reference(cfg, traffic, seed, precision="float32", fault=None,
              devices=None):
    """The first ``check_steps`` steps from the seed in plain jnp: loss of
    each step, per-leaf norm of the first gradient, per-leaf norm of the
    parameters' change after the last. ``precision`` other than float32 is
    the control; ``fault='half_batch'`` leaves half of every batch out and
    takes the mean over the rest, ``fault='no_exchange'`` keeps one chip's
    rows (the exchange between chips left out). A cell on several chips gets its rows
    spread over them, only so that the batch fits: the arithmetic is that of
    one device over the whole batch."""
    import json
    step = _reference_step(json.dumps(cfg, sort_keys=True), precision)
    sharding = None
    if traffic["chips"] > 1:
        from jax.sharding import Mesh, NamedSharding, PartitionSpec
        mesh = Mesh(np.array(list(devices)[:traffic["chips"]]), ("rows",))
        sharding = NamedSharding(mesh, PartitionSpec("rows"))
    start = init_params(cfg, seed)
    p, mom = start, jax.tree_util.tree_map(jnp.zeros_like, start)
    losses, grad_norms = [], None
    batches = make_batches(cfg, traffic, seed, traffic["check_steps"])
    for k, (x, y) in enumerate(batches):
        if fault == "half_batch":
            x, y = x[:len(x) // 2], y[:len(y) // 2]
        elif fault == "no_exchange":    # each chip trains on its own rows
            own = len(x) // traffic["chips"]
            x, y = x[:own], y[:own]
        if sharding is not None and len(x) % traffic["chips"] == 0:
            x, y = jax.device_put(x, sharding), jax.device_put(y, sharding)
        with jax.default_matmul_precision("highest"):
            loss, p, mom, norms = step(p, mom, x, y)
        losses.append(float(loss))
        if k == 0:
            grad_norms = {n: float(v)
                          for n, v in jax.device_get(norms).items()}
    delta = {n: float(v)
             for n, v in jax.device_get(_delta_norms(p, start)).items()}
    return {"losses": losses, "grad_norms": grad_norms, "delta_norms": delta}
