"""The main path's Pallas kernels, compiled at their real widths for a
described TPU v5e (2x2, nothing attached): what the chip's compiler
refuses — a block off the (8, 128) tiling, more scoped VMEM than a
kernel may take — fails here, at no chip time.

Nothing runs, so these say nothing about results; interpret-mode parity
lives in test_pallas_kernels.py / test_ragged.py and the on-chip
comparison in chip_smoke.py. ``jax.default_backend()`` is still ``cpu``
in this process, so each case calls the function that builds the
``pallas_call`` (explicit ``interpret=False``), not its dispatcher.

The topology is described inside module-scoped fixtures only (guide
on-chip-measurement §2): one process at a time may load the TPU
library, every xdist worker imports this file, and only the worker that
runs it may make the call. Keep every such test in THIS file.
"""
import functools
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from mxnet_tpu.ops.pallas import attention, lstm, moe_rows, rotary
from mxnet_tpu.ops.registry import get_op
from mxnet_tpu.parallel import moe


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to JAX's persistent
    cache but cannot be read back without the chip (the next run warns
    and recompiles): keep the cache off around these tests."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _lstm_cell(n, hdim, dtype):
    def build(struct):
        args = (struct((n, 4 * hdim), dtype), struct((n, hdim), dtype),
                struct((n, hdim), dtype), struct((4 * hdim, hdim), dtype))
        return (lambda x, h, c, w: lstm._cell_pallas(
            x, h, c, w, interpret=False)), args
    return build


def _lstm_layer(t, n, hdim, state_dtype, w_dtype, walk_kernels):
    """One direction of one layer, forward and backward, as ``jax.grad`` of
    the RNN op runs it on the chip; ``walk_kernels``: whether W_hh fits in
    VMEM, so that one kernel walks the time steps each way."""
    def fwd_bwd(xproj, h0, c0, w, dout):
        (out, hT, cT), vjp = jax.vjp(
            lambda *a: lstm.lstm_recurrence(*a, impl="pallas"),
            xproj, h0, c0, w)
        return out, vjp((dout, hT, cT))

    def build(struct):
        state = struct((n, hdim), state_dtype)
        return fwd_bwd, (struct((t, n, 4 * hdim), jnp.float32), state, state,
                         struct((4 * hdim, hdim), w_dtype),
                         struct((t, n, hdim), state_dtype))
    build.walk_kernels = walk_kernels
    return build


def _dense(b, h, s, d, backward):
    scale = 1.0 / d ** 0.5

    def fwd(q, k, v):
        return attention._flash_dense_pallas(q, k, v, True, scale, 256, 512,
                                             interpret=False)

    def fwd_bwd(q, k, v, do):
        # what jax.grad of flash_attention runs on the chip: the kernel
        # forward, then the blockwise jnp backward of its custom_vjp
        out = fwd(q, k, v)
        return out, attention._blockwise_bwd(q, k, v, out, do, True, scale,
                                             512)

    def build(struct):
        qkv = struct((b, h, s, d), jnp.bfloat16)
        if backward:
            return fwd_bwd, (qkv, qkv, qkv, qkv)
        return fwd, (qkv, qkv, qkv)
    return build


def _masked(b, h, s, d, with_lengths):
    scale = 1.0 / d ** 0.5

    def build(struct):
        qkv = struct((b, h, s, d), jnp.bfloat16)
        if with_lengths:
            return (lambda q, k, v, lens: attention._masked_pallas(
                q, k, v, lens, None, True, scale, 256, 512,
                interpret=False)), (qkv, qkv, qkv, struct((b,), jnp.int32))
        return (lambda q, k, v, seg: attention._masked_pallas(
            q, k, v, None, seg, False, scale, 256, 512,
            interpret=False)), (qkv, qkv, qkv, struct((b, s), jnp.int32))
    return build


def _gqa(heads, s, window, backward, d=128, rows=1, kv=8, block_length=0):
    """The decoder's attention at Laguna-XS.2's widths: ``heads`` query
    heads over 8 key/value heads of 128, one document of ``s`` positions;
    at LFM2-8B-A1B's: heads of ``d`` = 64, ``rows`` = 2 documents; or at
    SDAR-30B-A3B's: 32 heads over ``kv`` = 4 under the block-diffusion
    mask, ``s`` the noisy and the clean copy of a document together."""
    scale = 1.0 / d ** 0.5

    def fwd(q, k, v):
        return attention._gqa_pallas(q, k, v, True, window, scale, 512, 512,
                                     interpret=False,
                                     block_length=block_length)

    def fwd_bwd(q, k, v, do):
        # what jax.grad of grouped_query_attention runs on the chip: the
        # kernel, then the blockwise jnp backward reading its logsumexp
        out, lse = fwd(q, k, v)
        return out, attention._gqa_blockwise_bwd(q, k, v, out, lse, do, True,
                                                 window, scale, 512,
                                                 block_length)

    def build(struct):
        q = struct((rows, heads, s, d), jnp.bfloat16)
        keys = struct((rows, kv, s, d), jnp.bfloat16)
        return (fwd_bwd, (q, keys, keys, q)) if backward \
            else (fwd, (q, keys, keys))
    return build


_CASES = {
    "lstm-n64-h1024-f32": _lstm_cell(64, 1024, jnp.float32),
    "lstm-n64-h1024-bf16": _lstm_cell(64, 1024, jnp.bfloat16),
    "lstm-n64-h2048-bf16": _lstm_cell(64, 2048, jnp.bfloat16),
    # H off the 128-lane tiling takes the whole-array kernel:
    # examples/rnn/lstm_bucketing.py's default, an odd width, PTB-medium
    "lstm-n32-h64-f32": _lstm_cell(32, 64, jnp.float32),
    "lstm-n32-h200-bf16": _lstm_cell(32, 200, jnp.bfloat16),
    "lstm-n20-h650-f32": _lstm_cell(20, 650, jnp.float32),
    # the LM cell's layer (perfbench lstm-ptb-large.train-fed-seq128; 40 s:
    # the kernels' gate slices are off the lane tiling)
    "lstm-layer-t8-n256-h1500-bf16-weight": _lstm_layer(
        8, 256, 1500, jnp.float32, jnp.bfloat16, walk_kernels=True),
    "lstm-layer-t32-n64-h1024-bf16": _lstm_layer(
        32, 64, 1024, jnp.bfloat16, jnp.bfloat16, walk_kernels=True),
    # W_hh is 128 MiB: lax.scan of the per-step kernel, the jnp walk back
    "lstm-layer-t8-n64-h4096-bf16": _lstm_layer(
        8, 64, 4096, jnp.bfloat16, jnp.bfloat16, walk_kernels=False),
    "flash-b4h16s2048d64-fwd": _dense(4, 16, 2048, 64, backward=False),
    "flash-b4h16s2048d64-fwd-bwd": _dense(4, 16, 2048, 64, backward=True),
    "flash-b1h8s32768d128-fwd": _dense(1, 8, 32768, 128, backward=False),
    "gqa-h64kv8s8192d128-window512-fwd": _gqa(64, 8192, 512, False),
    "gqa-h64kv8s8192d128-window512-fwd-bwd": _gqa(64, 8192, 512, True),
    "gqa-h48kv8s8192d128-full-fwd-bwd": _gqa(48, 8192, 0, True),
    # half a lane tile a head (perfbench lfm2-8b-a1b.train-fed-2x8k)
    "gqa-b2h32kv8s8192d64-full-fwd-bwd": _gqa(32, 8192, 0, True, d=64,
                                              rows=2),
    # the walk of the mask's live tiles, 288 of 1,024 (perfbench
    # sdar-30b-a3b.train-fed-bd4-8k: 8,192 noisy + 8,192 clean rows)
    "gqa-h32kv4s16384d128-block-diffusion4-fwd-bwd": _gqa(
        32, 16384, 0, True, kv=4, block_length=4),
    "masked-b4h16s2048d64-lengths": _masked(4, 16, 2048, 64, True),
    "masked-b4h16s2048d64-segment-ids": _masked(4, 16, 2048, 64, False),
}


@pytest.mark.parametrize("case", list(_CASES))
def test_kernel_compiles_for_v5e(case, one_chip, no_compile_cache):
    def struct(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, args = _CASES[case](struct)
    # conftest turns x64 on for the numeric checks; the chip runs with it
    # off, and Mosaic takes no int64 block index
    with jax.enable_x64(False):
        compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert ("gqa_block_diffusion_attention" in text) \
        == ("block-diffusion" in case)
    walk_kernels = getattr(_CASES[case], "walk_kernels", None)
    if walk_kernels is not None:
        assert ("lstm_cell_scan" in text) == walk_kernels
        assert ("lstm_bwd_step" in text) == walk_kernels


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs inside its equations'
    parameters (the branches of a ``pl.when``, a ``jit`` inside)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


@pytest.mark.parametrize("case", [
    "gqa-h64kv8s8192d128-window512-fwd", "gqa-h48kv8s8192d128-full-fwd-bwd",
    "gqa-b2h32kv8s8192d64-full-fwd-bwd",
    "gqa-h32kv4s16384d128-block-diffusion4-fwd-bwd"])
def test_gqa_kernel_keeps_its_rows_along_sublanes(case):
    """The relayouts PR 34 took out cannot come back unnoticed: in the
    forward kernel's jaxpr the running state is two-dimensional from load to
    store. JAX writes a reduction that keeps its dimension as a reduce to
    one dimension and a ``broadcast_in_dim`` back, so that pair is what a
    one-dimensional value may be: made by a reduction, read by nothing but
    the broadcast that gives the dimension back. Needs no chip and no
    described one."""
    fn, args = _CASES[case](jax.ShapeDtypeStruct)
    with jax.enable_x64(False):
        traced = jax.make_jaxpr(lambda q, k, v, *_: attention._gqa_pallas(
            q, k, v, True, 512 * ("window" in case), 0.125, 512, 512, False,
            4 * ("block-diffusion" in case)))(*args)
    call, = [e for e in traced.jaxpr.eqns if e.primitive.name == "pallas_call"]
    eqns = list(_eqns(call.params["jaxpr"]))
    flat = {}
    for eqn in eqns:
        for var in eqn.outvars:
            if len(var.aval.shape) < 2 and var.aval.shape != ():
                assert eqn.primitive.name.startswith("reduce_"), eqn
                flat[var] = eqn
    assert flat       # the row max and sum of every slice, the lse's sums
    for eqn in eqns:
        for var in eqn.invars:
            if not hasattr(var, "val") and var in flat:    # no literal
                assert eqn.primitive.name == "broadcast_in_dim" \
                    and len(eqn.outvars[0].aval.shape) == 2, eqn


# -- what the routed layer moves around its grouped matmuls -------------------

_ITEM_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
               "u16": 2, "f32": 4, "s32": 4, "u32": 4, "s64": 8, "u64": 8}
_ARRAY = re.compile(r"\b(%s)\[([0-9,]*)\]" % "|".join(_ITEM_BYTES))
_INSTRUCTION = re.compile(
    r"(?:ROOT )?%([\w.\-]+) = (.*?) ([a-z][a-z\-]*)\((.*)$")
# no traffic of their own: names for what is there already
_FREE = ("parameter", "constant", "tuple", "get-tuple-element", "bitcast")


def _arrays(text):
    """(dtype, elements) of every array type written in ``text``."""
    return [(m.group(1),
             math.prod(int(n) for n in m.group(2).split(",") if n))
            for m in _ARRAY.finditer(text)]


def _entry_traffic(hlo):
    """(name, opcode, result arrays, operand + result bytes) of every
    instruction of the compiled module's entry computation outside the
    grouped-matmul kernels: what the compiler decided to write down and
    read back. An asynchronous copy (``*-start`` / ``*-done``: the
    compiler's prefetch between memory spaces) is left out, as ISSUE 32's
    table left it."""
    lines = hlo.splitlines()
    at = next(i for i, line in enumerate(lines) if line.startswith("ENTRY"))
    results, found = {}, []
    for line in lines[at + 1:]:
        if line.startswith("}"):
            break
        m = _INSTRUCTION.match(line.strip())
        if not m:
            continue
        name, result, opcode, rest = m.groups()
        results[name] = _arrays(result)
        if opcode in _FREE or opcode.endswith(("-start", "-done")) \
                or name.startswith("ragged-dot-none"):
            continue
        # the operands are names: the list ends at the first parenthesis
        operands = [a for n in re.findall(r"%([\w.\-]+)", rest.split(")")[0])
                    for a in results.get(n, [])]
        found.append((name, opcode, results[name], sum(
            _ITEM_BYTES[t] * n for t, n in results[name] + operands)))
    return found


_ROUTED_LAYERS = {
    # tokens, d, f, held of experts, top_k; GB outside the grouped matmuls
    # through the row kernels (6.66 / 7.76 / 10.62 as compiled; through jnp
    # 7.03 / 7.93, and 7.80 / 12.38 before the slots were choice-major)
    "laguna-xs2-t8192-k8-32of256": ((8192, 2048, 512, 32, 256, 8), 7.3),
    "lfm2-8b-a1b-t16384-k4-8of32": ((16384, 2048, 1792, 8, 32, 4), 8.5),
    "sdar-30b-a3b-t16384-k8-16of128": ((16384, 2048, 768, 16, 128, 8), 11.7),
}


@pytest.fixture
def row_kernels(monkeypatch):
    """The routed layer down the chip's path: it takes the row kernels only
    where ``jax.default_backend()`` is ``tpu``."""
    monkeypatch.setattr(moe_rows, "row_plan", functools.partial(
        moe_rows.row_plan, impl="pallas"))


@pytest.mark.parametrize("case", list(_ROUTED_LAYERS))
def test_routed_layer_moves_no_slot_tensor_it_need_not(case, one_chip,
                                                       no_compile_cache,
                                                       row_kernels):
    """One routed layer, output and gradient (bf16 tokens, float32 master
    weights), compiled for the described chip at a decoder cell's shape
    through the row kernels. From the compiled text: the two kernels are
    there both ways, outside the grouped matmuls nothing is written in
    float32 at the size of the slot rows (T k d), no ``reshape`` or
    ``copy`` relays a tensor of that size (a token's k slots as the
    second-minor axis of a tile did both), and all that is read and written
    there stays under the case's bound."""
    (t, d, f, held, experts, k), bound = _ROUTED_LAYERS[case]

    def loss(x, router, w_gate, w_up, w_down, ct):
        y, _ = moe.held_experts_apply(x, router, w_gate, w_up, w_down,
                                      num_experts=experts, top_k=k)
        return jnp.sum(y.astype(jnp.float32) * ct.astype(jnp.float32)), y

    def struct(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    tokens = struct((t, d), jnp.bfloat16)
    args = (tokens, struct((experts, d), jnp.float32),
            struct((held, d, f), jnp.float32),
            struct((held, d, f), jnp.float32),
            struct((held, f, d), jnp.float32), tokens)
    # as the chip runs it: conftest's "highest" is for the CPU's numerics,
    # and the grouped-matmul kernel takes no float32-precision bf16 matmul
    with jax.enable_x64(False), jax.default_matmul_precision("default"):
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4), has_aux=True)
                       ).lower(*args).compile().as_text()
    # forward 3, backward 6: every choice's row still rides in each
    assert len(set(re.findall(r"%(ragged-dot-none[.\d]*) = ", text))) == 9
    # the gather and the gather-sum (two kernels) forward, their transposes
    # backward
    for kernel in ("moe_rows_to_slots", "moe_rows_to_slot_order",
                   "moe_slots_to_rows"):
        assert len(set(re.findall(r"%%(%s[.\d]*) = " % kernel, text))) == 2
    traffic = _entry_traffic(text)
    slot_rows = t * k * d
    wide = [(name, arrays) for name, _, arrays, _ in traffic
            if any(dt == "f32" and n >= slot_rows for dt, n in arrays)]
    assert not wide, f"float32 at the slot rows' size: {wide}"
    relaid = [(name, arrays) for name, opcode, arrays, _ in traffic
              if opcode in ("reshape", "copy")
              and any(n == slot_rows for _, n in arrays)]
    assert not relaid, f"a relayout of the slot rows: {relaid}"
    moved = sum(b for *_, b in traffic) / 1e9
    assert moved < bound, f"{moved:.2f} GB outside the grouped matmuls"


def test_row_kernels_do_not_grow_with_the_rows(one_chip):
    """Each row kernel's Mosaic module, as the step's lowering carries it,
    is the same size at two token counts and two k (to the digits of the
    shapes it names): the kernels walk their rows in loops, so neither the
    lowering nor the stored executable grows with T or k (a row loop
    unrolled in Python would make both grow with the rows)."""
    def modules(t, k, d=2048):
        plan = moe_rows.row_plan(t, d, jnp.bfloat16, impl="pallas")

        def struct(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

        x, rows = struct((t, d), jnp.bfloat16), struct((k * t, d),
                                                       jnp.bfloat16)
        idx, scale = struct((k * t,), jnp.int32), struct((k * t,),
                                                         jnp.float32)
        flags = struct((k * t,), jnp.bool_)
        weights, held = struct((k, t), jnp.float32), struct((k, t), jnp.bool_)
        calls = (
            (lambda x, i: moe_rows.rows_to_slots(x, i, plan), (x, idx)),
            (lambda x, i, s, h, o: moe_rows.rows_to_slots(
                x, i, plan, s, h, o), (x, idx, scale, flags, rows)),
            (lambda r, i: moe_rows.slots_to_rows(r, i, k, plan), (rows, idx)),
            (lambda r, i, w, h: moe_rows.slots_to_rows(
                r, i, k, plan, w, h), (rows, idx, weights, held)))
        with jax.enable_x64(False):
            return [[len(c) for c in re.findall(
                r'backend_config = "([^"]*)"',
                jax.jit(fn).lower(*args).as_text())] for fn, args in calls]

    small, large = modules(4096, 4), modules(8192, 8)
    assert all(m for m in small), small
    # the same module but for the digits of the shapes it names
    for a, b in zip(sum(small, []), sum(large, [])):
        assert abs(b - a) <= 0.01 * a, (small, large)


def test_a_process_traces_each_row_kernel_once_a_shape(row_kernels):
    """Four routed layers of one shape, traced as shape inference traces a
    step at bind (in every process, before the stored step is loaded): each
    row kernel's call is traced once, not once a layer. Traced a layer at a
    time, the kernels cost Laguna's warm set-up 3.1 s on a TPU v5e
    (``jax.trace`` outside any program, 1.06 -> 3.29 s); once a shape, 0.15
    s."""
    from mxnet_tpu import profiler
    t, d, f, held, experts, k = 8192, 2048, 512, 32, 256, 8
    args = tuple(jax.ShapeDtypeStruct(shape, dtype) for shape, dtype in (
        ((t, d), jnp.bfloat16), ((experts, d), jnp.float32),
        ((held, d, f), jnp.float32), ((held, d, f), jnp.float32),
        ((held, f, d), jnp.float32)))

    def layers(x, *weights):
        for _ in range(4):
            x = moe.held_experts_apply(x, *weights, num_experts=experts,
                                       top_k=k)[0]
        return x

    jax.clear_caches()
    before = profiler.counters().get("moe.row_kernel_calls", 0)
    with jax.enable_x64(False):
        jax.eval_shape(layers, *args)
    # the gather and the weighted gather-sum
    assert profiler.counters().get("moe.row_kernel_calls", 0) - before == 2


# -- what RotaryEmbedding moves: one read and one write -----------------------

_YARN = dict(rope_type="yarn", factor=32.0, original_max_position=4096,
             attention_factor=1.2)
_ROTATIONS = {
    # (B, S, heads * head_dim), the op's attributes (ISSUE 36's table: the
    # jnp formulation moved 1.9 / 1.3 / 1.9 / 0.19 GB forward and 12-17%
    # more in jax's transpose)
    "laguna-sliding-q-8192x64x128": ((1, 8192, 64 * 128), dict(head_dim=128)),
    "laguna-full-q-8192x48x128-half-yarn": (
        (1, 8192, 48 * 128),
        dict(head_dim=128, rotary_dim=64, theta=5e5, **_YARN)),
    "sdar-q-16384x32x128-two-copies": (
        (1, 16384, 32 * 128), dict(head_dim=128, theta=1e6, copies=2)),
    "sdar-k-16384x4x128-two-copies": (
        (1, 16384, 4 * 128), dict(head_dim=128, theta=1e6, copies=2)),
}


@pytest.fixture
def rotary_kernel(monkeypatch):
    """``RotaryEmbedding`` down the chip's path: the op takes the kernel
    only where ``jax.default_backend()`` is ``tpu``."""
    monkeypatch.setattr(rotary, "kernel_plan", functools.partial(
        rotary.kernel_plan, impl="pallas"))


@pytest.mark.parametrize("stage", ["forward", "vjp"])
@pytest.mark.parametrize("case", list(_ROTATIONS))
def test_rotary_embedding_moves_one_read_and_one_write(
        case, stage, one_chip, no_compile_cache, rotary_kernel):
    """The op and its ``vjp`` between neighbours that keep the heads apart
    (a projection's output, the attention's operands: the transpositions
    cancel against the kernel's own), each compiled for the described chip at
    a decoder cell's shape, bf16 in and out: the kernel is in the program,
    everything the entry computation reads and writes stays under 1.3 x
    (input + output) plus the angle table built, written and read (three
    tables' bytes), and no float32 array of the tensor's size is written
    anywhere: the four-dimensional view's relayout cannot come back
    unnoticed. (LFM2's head of 64 keeps the jnp formulation.)"""
    (b, s, e), attrs = _ROTATIONS[case]
    d = attrs["head_dim"]
    op = get_op("RotaryEmbedding").fn

    def by_head(x):                   # (B, heads, S, d) in and out
        out = op(x.transpose(0, 2, 1, 3).reshape(b, s, e), **attrs)
        return out.reshape(b, s, e // d, d).transpose(0, 2, 1, 3)

    x = jax.ShapeDtypeStruct((b, e // d, s, d), jnp.bfloat16,
                             sharding=one_chip)
    if stage == "forward":
        fn, args = by_head, (x,)
    else:
        fn, args = (lambda x, g: jax.vjp(by_head, x)[1](g)[0]), (x, x)
    with jax.enable_x64(False):
        text = jax.jit(fn).lower(*args).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    elements = b * s * e
    wide = [(dt, n) for dt, n in _arrays(text) if dt == "f32" and n >= elements]
    assert not wide, f"float32 at the tensor's size: {wide}"
    moved = sum(b for *_, b in _entry_traffic(text))
    table = s // attrs.get("copies", 1) * d * 4
    assert moved < 1.3 * 2 * 2 * elements + 3 * table, \
        f"{moved / 1e9:.3f} GB for {2 * 2 * elements / 1e9:.3f} in and out"


_Q_PATHS = {
    # positions, model width, heads, head size, q norm, the op's attributes;
    # GB as compiled (PR 36; through the jnp formulation 5.57 / 3.17 / 2.81,
    # through a kernel over the (B S, heads x head size) view 6.44 / 2.34 /
    # 1.78)
    "sdar-q-norm-rotary-heads": (
        (16384, 2048, 32, 128, True, dict(theta=1e6, copies=2)), 2.6),
    "laguna-sliding-q-rotary-heads": (
        (8192, 2048, 64, 128, False, dict(theta=1e4)), 1.87),
    "laguna-full-q-rotary-heads": (
        (8192, 2048, 48, 128, False,
         dict(rotary_dim=64, theta=5e5, **_YARN)), 1.43),
}


@pytest.mark.parametrize("case", list(_Q_PATHS))
def test_query_path_keeps_its_heads_apart(case, one_chip, no_compile_cache,
                                          rotary_kernel):
    """Projection, q norm where the model has one, rotation and the
    attention's head layout, output and gradient, compiled for the described
    chip at a decoder cell's shape: the projection writes its heads apart,
    the kernel reads and writes them so, and all that the path moves stays
    under the case's bound. A kernel whose operand's layout XLA has to meet
    with copies of its own (position-minor float32 between the norm and the
    kernel: SDAR's step lost 1.5% to them) fails here at no chip time."""
    (s, width, heads, d, norm, attrs), bound = _Q_PATHS[case]
    rms, rope = get_op("RMSNorm").fn, get_op("RotaryEmbedding").fn

    def path(u, w, gain):
        q = jnp.dot(u, w.astype(jnp.bfloat16).T)
        if norm:
            q = rms(q.reshape(1, s, heads, d), gain, eps=1e-6) \
                .reshape(1, s, heads * d)
        q = rope(q, head_dim=d, **attrs)
        return q.reshape(1, s, heads, d).transpose(0, 2, 1, 3)

    def both(u, w, gain, ct):
        out, vjp = jax.vjp(path, u, w, gain)
        return out, vjp(ct)

    def struct(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (struct((1, s, width), jnp.bfloat16),
            struct((heads * d, width), jnp.float32), struct((d,), jnp.float32),
            struct((1, heads, s, d), jnp.bfloat16))
    with jax.enable_x64(False), jax.default_matmul_precision("default"):
        text = jax.jit(both).lower(*args).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    moved = sum(b for *_, b in _entry_traffic(text)) / 1e9
    assert moved < bound, f"{moved:.2f} GB along the query path"
