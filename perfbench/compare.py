"""The comparison that decides ``correct`` for a training cell.

Two records are compared, the program's and the reference's, each with the
loss of every checked step, the per-leaf norm of the first gradient as the
optimizer got it, and the per-leaf norm of the parameters' change after the
checked steps. A leaf's gap is | ||prog|| - ||ref|| | over the larger of
||ref|| of that leaf and ||ref|| of the median leaf (some gradients are all
but zero). A leaf whose reference gradient is under a thousandth of the
median leaf's is moved by round-off alone and is left out of the change, by
that rule and not by name.

Numbers (a cell's ``perfbench/limits/<cell>.json`` names the ones it holds,
each with its limit; PERF.md section 2 gives the readings behind them):

* ``loss_gap``          largest |program - reference| loss over the checked
  steps, as a share of the reference's;
* ``grad_gap_worst``    the first gradient's gap on the worst leaf;
* ``grad_gap_median``   ... on the median leaf;
* ``delta_gap_worst``   the change's gap on the worst leaf that moves;
* ``delta_gap_median``  ... on the median leaf that moves.
"""
import math
import statistics


def _leaf_gaps(prog, ref, keep=None):
    names = [n for n in ref if keep is None or n in keep]
    floor = statistics.median(ref[n] for n in names)
    out = {}
    for n in names:
        gap = abs(prog[n] - ref[n]) / max(ref[n], floor, 1e-30)
        out[n] = gap if math.isfinite(gap) else math.inf
    return out


def gaps(prog, ref):
    """``(numbers, where)``: the five numbers, and for the log the leaf that
    read worst in each family and how many leaves the rule left out."""
    loss_gap = max(abs(a - b) / abs(b)
                   for a, b in zip(prog["losses"], ref["losses"]))
    if not math.isfinite(loss_gap):
        loss_gap = math.inf
    grad = _leaf_gaps(prog["grad_norms"], ref["grad_norms"])
    med = statistics.median(ref["grad_norms"].values())
    moved = {n for n, v in ref["grad_norms"].items() if v >= 1e-3 * med}
    delta = _leaf_gaps(prog["delta_norms"], ref["delta_norms"], moved)
    numbers = {"loss_gap": loss_gap,
               "grad_gap_worst": max(grad.values()),
               "grad_gap_median": statistics.median(grad.values()),
               "delta_gap_worst": max(delta.values()),
               "delta_gap_median": statistics.median(delta.values())}
    where = {"grad_gap_worst": max(grad, key=grad.get),
             "delta_gap_worst": max(delta, key=delta.get),
             "leaves_left_out": len(grad) - len(moved)}
    return numbers, where


def judge(numbers, limits):
    """``(correct, checks)``: every number the cell holds beside its limit;
    correct only if each is at or under its limit."""
    checks = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    return ok, checks
