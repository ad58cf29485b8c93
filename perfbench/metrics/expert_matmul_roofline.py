"""The routed experts' grouped matmuls as a share of their roofline.

The work counted is what the routing asked for, whatever implements it. The
token-choices that fell on the experts held here (the program's own counter
``moe.assignments_held``, summed over the routed layers and the window's
steps) are the rows; a grouped matmul over ``rows`` of them against the held
experts' D x f weights is

* FLOPs = 2 x rows x D x f
* bytes = the held experts' weight (or its gradient) moved once
  (Eh x D x f x 2) + the rows in and out (rows x (D + f) x 2)
* least time = max(FLOPs / peak FLOP/s, bytes / peak HBM B/s), at the mean
  rows of a layer and step

times the number of such matmuls the trace shows (3 a layer in the forward,
as many again where the layer is recomputed, 6 in the backward: 2 for each,
its input's gradient and its weight's), over the summed device time of those
kernels on the busiest chip. They are found by the name the compiler gives
them (``blocks.grouped_matmul``): the program's ``MoEFFN/<node>/experts``
scope does not reach kernels the compiler makes for ``jax.lax.ragged_dot``,
and the forward's cannot be told from the backward's. A grouped matmul that
runs every row of its worst-case buffer reads low, as the program's does
(``held_experts_apply`` gives it all 8 T choices so that a step's time does
not follow the routing): about 75% x the held share of them. Nothing to read
without the counter or without such kernels."""
from perfbench import blocks


def work(cfg, rows):
    """(FLOPs, bytes) of one grouped matmul over ``rows`` token-choices."""
    D, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    return 2 * rows * D * f, \
        cfg["num_experts_held"] * D * f * 2 + rows * (D + f) * 2


def least_seconds(cfg, rows, peaks):
    flops, nbytes = work(cfg, rows)
    by_flops = flops / peaks["bf16_flops_per_s"]
    by_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return max(by_flops, by_bytes), \
        ("compute" if by_flops >= by_bytes else "memory")


def read(ctx):
    cfg, routed = ctx["cfg"], blocks.routed_window(ctx)
    steps = ctx["feed"]["batches"]
    if not routed or not steps:
        return None
    layers = cfg["mlp_layer_types"][:cfg["num_hidden_layers"]].count("sparse")
    seconds, count = ctx["trace"].seconds_matching(
        lambda name: blocks.grouped_matmul(name)
        and "metadata" not in blocks.scopes.instruction(name),
        ctx["trace"].busiest())
    if not count or seconds <= 0 or not layers:
        return None
    least, _ = least_seconds(
        cfg, routed["moe.assignments_held"] / (steps * layers), ctx["peaks"])
    return 100.0 * least * count / seconds
