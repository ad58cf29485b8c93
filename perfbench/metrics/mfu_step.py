"""The whole step's share of the chip's bf16 peak, from the device trace.

    steps x FLOPs per step per chip / (summed device time of the step
    program on the busiest chip x peak)

A step is one run of the program that takes most device time on the plane's
``XLA Modules`` line (``jit_step`` once a batch); its time is the program's
own span on the device, collectives and the device's waits inside it
included, and nothing of the time between steps. So a host stall lowers
``train_rate`` and raises ``device_idle_share`` and leaves this where it
was; a slower step lowers this. FLOPs per step are ``flops_per_item`` x
``items_per_batch`` of the configuration's model file (forward + backward
from the shapes, 2 per multiply-add, nothing recomputed counted), an equal
share to each chip; the peak is ``perfbench/peaks.json`` by device kind. A
trace with no step program returns nothing.
"""


def read(ctx):
    trace = ctx["trace"]
    steps = trace.steps()
    seconds = sum(end - start for start, end in steps)
    if not steps or seconds <= 0:
        return None
    model, cfg, traffic = ctx["model"], ctx["cfg"], ctx["traffic"]
    flops = model.flops_per_item(cfg) * model.items_per_batch(cfg, traffic)
    per_chip = flops / ctx["chips"]
    return 100.0 * len(steps) * per_chip / (
        seconds * ctx["peaks"]["bf16_flops_per_s"])
