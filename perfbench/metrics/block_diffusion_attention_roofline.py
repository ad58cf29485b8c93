"""The block-diffusion layers' attention, forward, as a share of its roofline.

The work counted is that of the mask, whatever tiles a kernel visits: over a
document of L tokens in blocks of B, run as a noisy and a clean copy, the
mask has L (L + B) live query-key pairs a head (the noisy half L B on its
block diagonal and L (L - B) / 2 before it, the clean half L (L + B) / 2),
each multiplied twice (scores, values):

* FLOPs = 2 x 2 x H x d x L (L + B) per document
* bytes = q and o (H x 2L x d each) + k and v (Hkv x 2L x d each), 2 bytes an
  element, each moved once
* least time = max(FLOPs / peak FLOP/s, bytes / peak HBM B/s)

over the device time of the operations scoped ``GroupedQueryAttention/`` in
the layers' first forward (the layout of the heads and the attention: what
computes the op's result), on the busiest chip, per step. A kernel that
walks dead tiles, or whole tiles for the 4 x 4 live squares of the noisy
diagonal, reads low. Nothing to read for a configuration with another
objective, or a program whose instructions name no block."""
from perfbench import blocks


def work(cfg, rows, seq_len):
    """(FLOPs, bytes) of one layer's forward attention."""
    H, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d, B = cfg["head_dim"], cfg["block_length"]
    flops = 2 * 2 * H * d * seq_len * (seq_len + B) * rows
    nbytes = 2 * (2 * H + 2 * kv) * 2 * seq_len * d * rows
    return flops, nbytes


def least_seconds(cfg, rows, seq_len, peaks):
    """Summed over the configuration's layers, and which bound."""
    flops, nbytes = work(cfg, rows, seq_len)
    by_flops = flops / peaks["bf16_flops_per_s"]
    by_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return cfg["num_hidden_layers"] * max(by_flops, by_bytes), \
        ("compute" if by_flops >= by_bytes else "memory")


def read(ctx):
    cfg, traffic = ctx["cfg"], ctx["traffic"]
    if cfg.get("objective") != "block_diffusion" or "seq_len" not in traffic:
        return None
    seconds = blocks.seconds(
        ctx, lambda block, op, part, stage:
        op == "GroupedQueryAttention" and stage == "forward")
    steps = len(ctx["trace"].steps())
    if not seconds or not steps:
        return None
    least, _ = least_seconds(cfg, traffic["per_chip_batch"],
                             traffic["seq_len"], ctx["peaks"])
    return 100.0 * least * steps / seconds
