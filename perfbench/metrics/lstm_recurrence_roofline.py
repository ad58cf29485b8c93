"""The LSTM recurrence's share of its roofline, from the device trace.

The work counted is one training step's *forward* recurrences, whatever
implements them: L layers x T time steps of ``h @ W_hh^T`` (N x H by H x 4H)
plus the gate arithmetic.

* FLOPs  = 2 * N * H * 4H * T * L            (the matmul; the gates' ~30
  flops per unit are under 1% and left out)
* bytes  = L * 4H * H * w                    (each layer's W_hh read ONCE a
  step: a kernel that keeps it on chip over the scan does no more, one that
  re-reads it every time step is charged the same work)
         + L * T * N * 4H * a                (the input projection read)
         + L * T * N * H * a                 (h written; c stays on chip)
  with w = a = 2 bytes (bf16 weights and activations, as the cell is run).
* least time = max(FLOPs / peak FLOP/s, bytes / peak HBM B/s); the larger
  one names the bound.

At N=256, H=1500, T=128, L=2: 1.18 TFLOP -> 5.99 ms at 197 TFLOP/s;
1.02 GB -> 1.24 ms at 819 GB/s: compute-bound. N and T are the traffic's
(``per_chip_batch``, ``seq_len``), H and L the configuration's.

Measured time: the summed device time of the operations named
``lstm_cell*`` (the Pallas kernel's ``name=``; the instruction's own name,
not a consumer's operand list) on one chip, over the traced
window, divided by the steps in it. A run whose trace names no such event
returns nothing (a later PR that takes the kernel off the path leaves this
silent; ``mfu_step`` still bounds it).
"""
import re

KERNEL = re.compile(r"lstm_cell")


def work(cfg, rows, seq_len):
    N, H = rows, cfg["hidden_size"]
    T, L = seq_len, cfg["num_layers"]
    flops = 2 * N * H * 4 * H * T * L
    nbytes = L * 4 * H * H * 2 + L * T * N * 4 * H * 2 + L * T * N * H * 2
    return flops, nbytes


def least_seconds(cfg, rows, seq_len, peaks):
    flops, nbytes = work(cfg, rows, seq_len)
    by_flops = flops / peaks["bf16_flops_per_s"]
    by_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return max(by_flops, by_bytes), \
        ("compute" if by_flops >= by_bytes else "memory")


def read(ctx):
    if "hidden_size" not in ctx["cfg"] or "seq_len" not in ctx["traffic"]:
        return None
    seconds, count = ctx["trace"].seconds_matching(
        lambda name: KERNEL.search(name.split(" = ")[0]) is not None)
    steps = ctx["feed"]["batches"]
    if not count or not steps or seconds <= 0:
        return None
    least, _ = least_seconds(ctx["cfg"], ctx["traffic"]["per_chip_batch"],
                             ctx["traffic"]["seq_len"], ctx["peaks"])
    return 100.0 * least * steps / seconds
