#!/usr/bin/env python
"""Where does the ResNet-50 step time go on this chip?

Measures, on the real TPU: (a) a big bf16 matmul (MXU ceiling), (b) every
unique ResNet-50 conv shape fwd and data/weight grads, (c) model fwd /
fwd+bwd / full SPMDTrainer step. Timings close with
``block_until_ready``. Prints a table with achieved TFLOP/s.
"""
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax import lax


from _device import require_chip

_sync = jax.block_until_ready


def timed(fn, *args, reps=3):
    """Best-of-reps wall time of one fn(*args), closed by
    block_until_ready — the shared discipline for the in-graph-loop
    benchmarks (convs/gemm/roofline import this; keep the sync semantics
    in one place)."""
    _sync(fn(*args))  # compile + settle
    best = 1e9
    for _ in range(reps):
        t0 = time.perf_counter()
        _sync(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def timeit(fn, *args, iters=10, warmup=2):
    for _ in range(warmup):
        out = fn(*args)
    _sync(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    _sync(out)
    return (time.perf_counter() - t0) / iters


# ResNet-50 NHWC conv shapes at batch B, 224x224:
# (H, W, Cin, Cout, k, stride)
def resnet50_convs():
    convs = [(224, 224, 3, 64, 7, 2)]  # stem
    # (bottleneck: 1x1 reduce, 3x3, 1x1 expand) x stages
    stages = [(56, 64, 256, 3), (28, 128, 512, 4),
              (14, 256, 1024, 6), (7, 512, 2048, 3)]
    cin = 64
    for hw, mid, out, blocks in stages:
        first_in_hw = hw * 2 if hw != 56 else 56
        for b in range(blocks):
            s = 2 if (b == 0 and hw != 56) else 1
            in_hw = first_in_hw if b == 0 else hw
            convs.append((in_hw, in_hw, cin, mid, 1, s))
            convs.append((hw, hw, mid, mid, 3, 1))
            convs.append((hw, hw, mid, out, 1, 1))
            if b == 0:
                convs.append((in_hw, in_hw, cin, out, 1, s))
            cin = out
    return convs


def conv_flops(B, h, w, cin, cout, k, s):
    oh, ow = h // s, w // s
    return 2 * B * oh * ow * cin * cout * k * k


def main():
    B = int(os.environ.get("BENCH_BATCH", "256"))
    print("device:", require_chip(), flush=True)
    rng = np.random.RandomState(0)

    # MXU ceiling: big bf16 matmul
    m = jnp.asarray(rng.rand(8192, 8192), jnp.bfloat16)
    mm = jax.jit(lambda a, b: a @ b)
    dt = timeit(mm, m, m)
    print(f"matmul 8192^3 bf16: {2 * 8192**3 / dt / 1e12:7.1f} TF/s")

    # conv zoo
    total_t = 0.0
    total_f = 0
    uniq = {}
    for shape in resnet50_convs():
        uniq[shape] = uniq.get(shape, 0) + 1
    print(f"\n{'HxW':>9} {'Cin':>4} {'Cout':>4} k s n | "
          f"{'fwd TF/s':>8} {'dgrad':>8} {'wgrad':>8} | ms(fwd,n)")
    for (h, w, cin, cout, k, s), n in sorted(uniq.items()):
        x = jnp.asarray(rng.rand(B, h, w, cin), jnp.bfloat16)
        wt = jnp.asarray(rng.rand(k, k, cin, cout), jnp.bfloat16)
        dn = lax.conv_dimension_numbers(x.shape, wt.shape,
                                        ("NHWC", "HWIO", "NHWC"))
        p = k // 2

        def f(x, wt):
            return lax.conv_general_dilated(
                x, wt, (s, s), [(p, p), (p, p)], dimension_numbers=dn)

        fj = jax.jit(f)
        flops = conv_flops(B, h, w, cin, cout, k, s)
        dtf = timeit(fj, x, wt)

        # grads via vjp
        g = jax.jit(lambda x, wt: jax.vjp(f, x, wt)[1](
            jnp.ones((B, h // s, w // s, cout), jnp.bfloat16)))
        # separate dgrad/wgrad hard to split; time the pair
        dtg = timeit(g, x, wt)
        total_t += n * (dtf + dtg)
        total_f += n * 3 * flops
        print(f"{h:4d}x{w:<4d} {cin:4d} {cout:4d} {k} {s} {n} | "
              f"{flops / dtf / 1e12:8.1f} {'--':>8} "
              f"{2 * flops / dtg / 1e12:8.1f} | "
              f"{dtf * 1e3:6.2f} {n * (dtf + dtg) * 1e3:6.1f}")
    print(f"\nsum conv fwd+bwd: {total_t * 1e3:.1f} ms, "
          f"{total_f / 1e9:.1f} GFLOP, {total_f / total_t / 1e12:.1f} TF/s")

    # full model: fwd / fwd+bwd / step
    from mxnet_tpu import models
    from mxnet_tpu.parallel import SPMDTrainer, make_mesh
    from mxnet_tpu.executor import build_graph_eval

    mesh = make_mesh({"data": 1}, devices=jax.devices()[:1])
    sym = models.get_symbol("resnet", num_layers=50, num_classes=1000,
                            image_shape="224,224,3", dtype="bfloat16")
    tr = SPMDTrainer(
        sym, optimizer="sgd",
        optimizer_params=dict(learning_rate=0.1, momentum=0.9,
                              rescale_grad=1.0 / B),
        mesh=mesh, compute_dtype="bfloat16")
    tr.bind(data_shapes={"data": (B, 224, 224, 3)},
            label_shapes={"softmax_label": (B,)})
    x = jax.device_put(rng.rand(B, 224, 224, 3).astype(np.float32),
                       tr._in_shardings["data"])
    y = jax.device_put(rng.randint(0, 1000, (B,)).astype(np.float32),
                       tr._in_shardings["softmax_label"])
    feed = {"data": x, "softmax_label": y}
    dt_step = timeit(lambda: tr.step(feed), iters=10)
    model_flops = 2 * 3 * B * 4.1e9  # fwd 4.1 GFLOP/img, bwd 2x
    print(f"\nfull step:  {dt_step * 1e3:7.1f} ms  "
          f"{B / dt_step:7.1f} img/s  "
          f"~{model_flops / dt_step / 1e12:5.1f} TF/s (fwd+bwd flops)")

    # fwd-only through the same executor
    eval_fn = build_graph_eval(sym)
    arg_shapes, _, aux_shapes = sym.infer_shape(
        data=(B, 224, 224, 3), softmax_label=(B,))
    params = {n: jnp.asarray(rng.normal(0, .02, sh).astype(np.float32))
              for n, sh in zip(sym.list_arguments(), arg_shapes)
              if n not in ("data", "softmax_label")}
    aux = {n: (jnp.ones(sh, np.float32) if n.endswith("var")
               else jnp.zeros(sh, np.float32))
           for n, sh in zip(sym.list_auxiliary_states(), aux_shapes)}

    @jax.jit
    def fwd(params, aux, x):
        merged = {n: (v.astype(jnp.bfloat16) if v.ndim >= 2 else v)
                  for n, v in params.items()}
        merged["data"] = x
        merged["softmax_label"] = jnp.zeros((x.shape[0],), jnp.float32)
        outs, _ = eval_fn(merged, aux, jax.random.PRNGKey(0), True)
        return outs[0]

    dt_fwd = timeit(fwd, params, aux, jnp.asarray(x))
    print(f"fwd only:   {dt_fwd * 1e3:7.1f} ms  "
          f"~{2 * B * 4.1e9 / dt_fwd / 1e12:5.1f} TF/s")

    @jax.jit
    def fwdbwd(params, aux, x, y):
        def loss_fn(p):
            merged = {n: (v.astype(jnp.bfloat16) if v.ndim >= 2 else v)
                      for n, v in p.items()}
            merged["data"] = x
            merged["softmax_label"] = y
            outs, _ = eval_fn(merged, aux, jax.random.PRNGKey(0), True)
            out = outs[0].astype(jnp.float32)
            lab = y.astype(jnp.int32)
            lp = jnp.log(jnp.clip(out, 1e-10))
            return -jnp.take_along_axis(lp, lab[:, None], 1).mean()
        l, g = jax.value_and_grad(loss_fn)(params)
        return l

    dt_fb = timeit(fwdbwd, params, aux, jnp.asarray(x), jnp.asarray(y))
    print(f"fwd+bwd:    {dt_fb * 1e3:7.1f} ms  "
          f"~{model_flops / dt_fb / 1e12:5.1f} TF/s")


if __name__ == "__main__":
    main()
