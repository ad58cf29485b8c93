"""The one general traffic generator for training cells.

A traffic file (``perfbench/traffic/<mix>.json``) says how batches reach the
step: ``feed: host`` is what a user runs, ``PrefetchingIter(NDArrayIter(host
float32))``; ``feed: resident`` yields arrays already placed where the step
reads them, which bypasses the input pipeline. Either way the program sees
only a ``DataIter``; :class:`Window` wraps it, times every ``next()`` and ends
the epoch when the armed number of batches or of seconds has passed.
"""
import time

import jax


class _Resident:
    """Cycles over batches that already live on the device."""

    def __init__(self, batches, shardings, names, io):
        data_name, label_name = names
        self._batches = [io.DataBatch(
            data=[jax.device_put(x, shardings[data_name])],
            label=[jax.device_put(y, shardings[label_name])], pad=0)
            for x, y in batches]
        jax.block_until_ready([b.data + b.label for b in self._batches])
        rows = batches[0][0].shape[0]
        self.batch_size = rows
        self.provide_data = [io.DataDesc(data_name, batches[0][0].shape)]
        self.provide_label = [io.DataDesc(label_name, batches[0][1].shape)]
        self._k = 0

    def reset(self):
        self._k = 0

    def next(self):
        if self._k >= len(self._batches):
            raise StopIteration
        self._k += 1
        return self._batches[self._k - 1]


def inner_iterator(traffic, batches, shardings, names=("data",
                                                       "softmax_label")):
    """The iterator the cell's ``feed`` names, over the seed's batches."""
    from mxnet_tpu import io
    if traffic["feed"] == "resident":
        return _Resident(batches, shardings, names, io)
    if traffic["feed"] != "host":
        raise SystemExit(f"unknown feed {traffic['feed']!r}")
    import numpy as np
    data = np.concatenate([b[0] for b in batches])
    label = np.concatenate([b[1] for b in batches])
    return io.PrefetchingIter(io.NDArrayIter(
        data, label, batch_size=batches[0][0].shape[0],
        data_name=names[0], label_name=names[1]))


class Window:
    """A ``DataIter`` over ``inner`` that restarts it when it runs out and
    ends its own epoch after ``arm``'s batches or seconds. ``next()`` is
    timed; ``calls`` keeps the host clock of every call, from which a traced
    run tells whether a late step waited on ``next()`` or on the body of
    ``fit``."""

    def __init__(self, inner):
        self.inner = inner
        self.batch_size = inner.batch_size
        self.provide_data = inner.provide_data
        self.provide_label = inner.provide_label
        self.arm()

    def arm(self, batches=None, seconds=None, on_first=None):
        self._max_batches, self._seconds = batches, seconds
        self._on_first = on_first
        self.count = 0
        self.wait_s = 0.0
        self.started = None
        self.calls = []          # (start, end) of each next(), perf_counter
        self._fresh = True

    def reset(self):
        # fit() resets before its epoch; the armed limits stand
        if not self._fresh:
            self.inner.reset()
            self._fresh = True

    def __iter__(self):
        return self

    def __next__(self):
        return self.next()

    def next(self):
        t0 = time.perf_counter()
        if self.started is None:
            if self._on_first is not None:
                self._on_first()
                t0 = time.perf_counter()
            self.started = t0
        if (self._max_batches is not None
                and self.count >= self._max_batches) or (
                self._seconds is not None
                and t0 - self.started >= self._seconds):
            raise StopIteration
        self._fresh = False
        try:
            batch = self.inner.next()
        except StopIteration:
            self.inner.reset()
            batch = self.inner.next()
        t1 = time.perf_counter()
        self.wait_s += t1 - t0
        self.calls.append((t0, t1))
        self.count += 1
        return batch
