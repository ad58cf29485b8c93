"""A floor on the convolution kernels' share of the chip's bf16 peak, from
the device trace and the step program's op map.

    steps x (flops_per_item - the dense layers') x items per chip /
    (device time of the fusions rooted in a convolution, forward and
    transposed, on the busiest chip x peak)

FLOPs are the model file's count from the shapes (forward + backward, 2 per
multiply-add) less 3 x 2 x the size of every two-dimensional weight (a dense
layer applied once an item; a convolution's weight has four dimensions):
the reader knows no parameter by name. The model files state no convolution
count of their own yet (``conv_flops_per_item`` beside ``flops_per_item``
would be an edit to an accepted benchmark file: PERF.md, Open question 10).
The convolutions of ResNet-50 are compute-bound at bs256, so their least
time is FLOPs over the peak.

The time is that of the instructions scoped ``Convolution/...``: a fusion
is charged whole to the scope its own ``op_name`` carries, which XLA takes
from the fusion's root. So it holds whatever the compiler fused under a
convolution's root (on the chip: the BatchNorm forward and the ReLU that
follow it) and moves when fusion choices move; the convolutions alone run
at this share or above. Nothing to read for a model without
``param_shapes``, a trace without steps, or a program without an op map."""
from perfbench import scopes


def read(ctx):
    model, cfg, traffic = ctx["model"], ctx["cfg"], ctx["traffic"]
    shapes = getattr(model, "param_shapes", None)
    found = scopes.scoped_seconds(ctx)
    if shapes is None or found is None:
        return None
    by_scope = found[0]
    seconds = sum(s for (scope, _), s in by_scope.items()
                  if scopes.op_type(scope) == "Convolution")
    if seconds <= 0:
        return None
    dense = sum(3 * 2 * shape[0] * shape[1]
                for shape in shapes(cfg).values() if len(shape) == 2)
    flops = (model.flops_per_item(cfg) - dense) \
        * model.items_per_batch(cfg, traffic) / ctx["chips"]
    steps = len(ctx["trace"].steps())
    return 100.0 * steps * flops / (
        seconds * ctx["peaks"]["bf16_flops_per_s"])
