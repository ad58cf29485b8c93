"""The gated short convolution's memory-bound part, forward, as a share of
its roofline.

The work counted is that of ``c * conv(b * h)`` over (rows, S, D) streams,
whatever implements it (fused ``jnp`` or a kernel): three streams read, one
written, each once, in the compute dtype; the L taps and the two gates are
2 (L + 1) FLOPs an element, far under the machine's balance, so the bytes
bound it:

* bytes = 4 x rows x S x D x itemsize
* FLOPs = 2 x (L + 1) x rows x S x D
* least time = max(bytes / peak HBM B/s, FLOPs / peak FLOP/s)

times the ``conv`` layers and the steps, over the device time of the
operations scoped ``ShortConv/<node>/conv`` in the first forward, on the
busiest chip. A part that writes ``b * h`` or the padded stream out and
reads it back reads low. Nothing to read for a configuration without
``conv`` layers, or a program whose instructions name no such scope."""
from perfbench import blocks

_ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def work(cfg, rows, seq_len):
    """(FLOPs, bytes) of one layer's gated convolution, forward."""
    elements = rows * seq_len * cfg["hidden_size"]
    return 2 * (cfg["conv_L_cache"] + 1) * elements, \
        4 * elements * _ITEMSIZE[cfg["compute_dtype"]]


def least_seconds(cfg, rows, seq_len, peaks):
    """Of one layer, and which bound."""
    flops, nbytes = work(cfg, rows, seq_len)
    by_flops = flops / peaks["bf16_flops_per_s"]
    by_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return max(by_flops, by_bytes), \
        ("compute" if by_flops >= by_bytes else "memory")


def read(ctx):
    cfg, traffic = ctx["cfg"], ctx["traffic"]
    if "conv_L_cache" not in cfg or "seq_len" not in traffic:
        return None
    layers = cfg["layer_types"][:cfg["num_hidden_layers"]].count("conv")
    seconds = blocks.seconds(
        ctx, lambda block, op, part, stage: op == "ShortConv"
        and part == "conv" and stage == "forward")
    steps = len(ctx["trace"].steps())
    if not seconds or not steps or not layers:
        return None
    least, _ = least_seconds(cfg, traffic["per_chip_batch"],
                             traffic["seq_len"], ctx["peaks"])
    return 100.0 * least * layers * steps / seconds
