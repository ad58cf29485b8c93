"""The sliding-window layers' attention, forward, as a share of its roofline.

The work counted is that of the band, whatever implements it: every query
sees its window, so a layer of H query heads over S positions with window W
multiplies S W - W^2 / 2 query-key pairs a head, twice (scores, values):

* FLOPs = 2 x 2 x H x d x (S W - W^2 / 2) per document
* bytes = q and o (H x S x d each) + k and v (Hkv x S x d each), 2 bytes an
  element, each moved once
* least time = max(FLOPs / peak FLOP/s, bytes / peak HBM B/s)

over the device time of the operations scoped ``GroupedQueryAttention/`` in
the sliding layers' first forward (the layout of the heads, the attention,
the output gate: what computes the op's result), on the busiest chip, per
step. A kernel that computes the whole causal triangle and masks it reads
low. Nothing to read for a configuration without sliding layers, or a
program whose instructions name no block."""
from perfbench import blocks


def work(cfg, rows, seq_len, heads):
    """(FLOPs, bytes) of one sliding layer's forward attention."""
    d, W = cfg["head_dim"], min(cfg["sliding_window"], seq_len)
    pairs = seq_len * W - W * W / 2
    flops = 2 * 2 * heads * d * pairs * rows
    nbytes = 2 * (2 * heads + 2 * cfg["num_key_value_heads"]) \
        * seq_len * d * rows
    return flops, nbytes


def least_seconds(cfg, rows, seq_len, peaks):
    """Summed over the configuration's sliding layers, and which bound."""
    total, bound = 0.0, None
    n = cfg["num_hidden_layers"]
    for kind, heads in zip(cfg["layer_types"][:n],
                           cfg["num_attention_heads_per_layer"][:n]):
        if kind != "sliding_attention":
            continue
        flops, nbytes = work(cfg, rows, seq_len, heads)
        by_flops = flops / peaks["bf16_flops_per_s"]
        by_bytes = nbytes / peaks["hbm_bytes_per_s"]
        total += max(by_flops, by_bytes)
        bound = "compute" if by_flops >= by_bytes else "memory"
    return total, bound


def read(ctx):
    cfg, traffic = ctx["cfg"], ctx["traffic"]
    if "layer_types" not in cfg or "seq_len" not in traffic:
        return None
    n = cfg["num_hidden_layers"]
    sliding = {f"layer{k}" for k, kind in enumerate(cfg["layer_types"][:n])
               if kind == "sliding_attention"}
    seconds = blocks.seconds(
        ctx, lambda block, op, part, stage: block in sliding
        and op == "GroupedQueryAttention" and stage == "forward")
    steps = len(ctx["trace"].steps())
    if not seconds or not steps:
        return None
    least, _ = least_seconds(cfg, traffic["per_chip_batch"],
                             traffic["seq_len"], ctx["peaks"])
    return 100.0 * least * steps / seconds
