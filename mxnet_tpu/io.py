"""Data iterators.

Reference: python/mxnet/io.py (DataIter/DataBatch/DataDesc:41-175,
NDArrayIter:515, ResizeIter:277, PrefetchingIter:342) and the C++ iterators
under src/io/ (MNISTIter, CSVIter). The C-backed pipeline (RecordIO/image
decode) lives in io_record.py / the native lib; this module is the pure
python-facing iterator API.
"""
from __future__ import annotations

import threading
from collections import namedtuple
from typing import List, Optional

import numpy as _np

from . import profiler as _profiler
from .base import MXNetError
from .ndarray import NDArray, array as nd_array
from .resilience import guarded_point

__all__ = ["DataDesc", "DataBatch", "DataIter", "NDArrayIter", "ResizeIter",
           "PrefetchingIter", "MXDataIter", "MNISTIter", "CSVIter", "LibSVMIter",
           "ImageRecordIter"]


class DataDesc(namedtuple("DataDesc", ["name", "shape"])):
    """Name+shape(+dtype/layout) of one input (reference: io.py DataDesc)."""

    def __new__(cls, name, shape, dtype=_np.float32, layout="NCHW"):
        ret = super().__new__(cls, name, tuple(shape))
        ret.dtype = dtype
        ret.layout = layout
        return ret

    @staticmethod
    def get_batch_axis(layout):
        if layout is None:
            return 0
        return layout.find("N")

    @staticmethod
    def get_list(shapes, types):
        if types is not None:
            type_dict = dict(types)
            return [DataDesc(x[0], x[1], type_dict[x[0]]) for x in shapes]
        return [DataDesc(x[0], x[1]) for x in shapes]


class DataBatch:
    def __init__(self, data, label=None, pad=None, index=None,
                 bucket_key=None, provide_data=None, provide_label=None):
        self.data = data
        self.label = label
        self.pad = pad
        self.index = index
        self.bucket_key = bucket_key
        self.provide_data = provide_data
        self.provide_label = provide_label


class DataIter:
    """Iterator base (reference: io.py DataIter)."""

    def __init__(self, batch_size=0):
        self.batch_size = batch_size

    def __iter__(self):
        return self

    def reset(self):
        pass

    def next(self) -> DataBatch:
        if self.iter_next():
            return DataBatch(data=self.getdata(), label=self.getlabel(),
                             pad=self.getpad(), index=self.getindex())
        raise StopIteration

    def __next__(self):
        # the ``io.next`` fault site sits at the batch-fetch boundary and
        # injected retriable faults back off under the default policy; the
        # fetch itself runs exactly once, because iterators advance their
        # cursor in iter_next() before reading — blindly re-running next()
        # after a mid-fetch failure would silently drop a batch.
        guarded_point("io.next")
        return self.next()

    def iter_next(self):
        raise NotImplementedError()

    def getdata(self):
        raise NotImplementedError()

    def getlabel(self):
        raise NotImplementedError()

    def getindex(self):
        return None

    def getpad(self):
        raise NotImplementedError()


def _init_data(data, allow_empty, default_name):
    """Normalize to list of (name, NDArray) (reference: io.py _init_data)."""
    assert data is not None or allow_empty
    if data is None:
        data = []
    if isinstance(data, (_np.ndarray, NDArray)):
        data = [data]
    if isinstance(data, list):
        if not allow_empty:
            assert len(data) > 0
        if len(data) == 1:
            data = {default_name: data[0]}
        else:
            data = {f"_{i}_{default_name}": d for i, d in enumerate(data)}
    if not isinstance(data, dict):
        raise TypeError("Input must be NDArray, numpy.ndarray, a list of them "
                        "or dict with them as values")
    out = []
    for k, v in data.items():
        if not isinstance(v, NDArray):
            try:
                v = nd_array(_np.asarray(v, dtype=v.dtype if hasattr(v, "dtype")
                                         else _np.float32))
            except Exception as e:
                raise TypeError(f"Invalid type '{type(v)}' for {k}") from e
        out.append((k, v))
    return out


class NDArrayIter(DataIter):
    """Iterate over in-memory arrays (reference: io.py:515)."""

    def __init__(self, data, label=None, batch_size=1, shuffle=False,
                 last_batch_handle="pad", data_name="data",
                 label_name="softmax_label", seed=None):
        super().__init__(batch_size)
        # the sources go to the device (_init_data) and come back as the
        # host cache: one span, so a long start can be put down to it
        with _profiler.span("input.construct", args={}) as made:
            self._construct(data, label, batch_size, shuffle,
                            last_batch_handle, data_name, label_name, seed)
            made.args["bytes"] = sum(
                cached.nbytes for cached in self._np_cache.values())
        _profiler.count("input.construct_bytes", made.args["bytes"])

    def _construct(self, data, label, batch_size, shuffle, last_batch_handle,
                   data_name, label_name, seed):
        self.data = _init_data(data, allow_empty=False, default_name=data_name)
        self.label = _init_data(label, allow_empty=True, default_name=label_name)

        # an owned RandomState (not the process-global numpy RNG) so
        # state_dict() can snapshot the shuffle stream and a mid-epoch
        # resume replays the exact batch sequence; with seed=None the
        # seed is DRAWN from the global stream, so callers that
        # np.random.seed(0) for reproducibility keep getting the same
        # shuffle order run over run. The pristine pre-shuffle state
        # (_rng0) plus a shuffle counter makes state_dict O(1): a
        # restore replays the shuffles instead of serializing the
        # whole permutation.
        if shuffle:
            if seed is None:
                seed = _np.random.randint(0, 2**31 - 1)
            self._rng = _np.random.RandomState(seed)
            self._rng0 = self._rng.get_state()
        else:
            self._rng = None
            self._rng0 = None
        self._shuffles = 0
        self.idx = _np.arange(self.data[0][1].shape[0])
        if shuffle:
            self._rng.shuffle(self.idx)
            self._shuffles = 1
        self._shuffle = shuffle

        if last_batch_handle == "discard":
            new_n = self.data[0][1].shape[0] - self.data[0][1].shape[0] % batch_size
            self.idx = self.idx[:new_n]

        self.data_list = [x[1] for x in self.data] + [x[1] for x in self.label]
        # one host copy per source up front, never written afterwards: a
        # batch that is a run of rows is handed to the transfer as a view of
        # it (_getdata), which costs nothing; any other batch is an O(batch)
        # gather. A view, and on the CPU backend the jax.Array made from it,
        # alias this memory, so the arrays are read-only: a write raises
        # instead of changing batches already sent or still in flight.
        self._np_cache = {id(x): x.asnumpy()
                          for _, x in self.data + self.label}
        for cached in self._np_cache.values():
            cached.flags.writeable = False
        self.num_source = len(self.data_list)
        self.num_data = len(self.idx)
        assert self.num_data >= batch_size, \
            "batch_size needs to be smaller than data size"
        self.cursor = -batch_size
        self.last_batch_handle = last_batch_handle

    @property
    def provide_data(self) -> List[DataDesc]:
        return [DataDesc(k, (self.batch_size,) + tuple(v.shape[1:]),
                         v.dtype) for k, v in self.data]

    @property
    def provide_label(self) -> List[DataDesc]:
        return [DataDesc(k, (self.batch_size,) + tuple(v.shape[1:]),
                         v.dtype) for k, v in self.label]

    def hard_reset(self):
        self.cursor = -self.batch_size

    def reset(self):
        if self._shuffle:
            self._rng.shuffle(self.idx)
            self._shuffles += 1
        if self.last_batch_handle == "roll_over" and \
                self.cursor > self.num_data:
            self.cursor = -self.batch_size + (self.cursor % self.num_data) \
                % self.batch_size
        else:
            self.cursor = -self.batch_size

    # -- checkpointable state (resilience/data.py, mid-epoch resume) ---------

    def state_dict(self):
        """JSON-serializable position + shuffle state; restoring it with
        :meth:`load_state_dict` replays the exact remaining batch
        sequence (this epoch's permutation and every later shuffle).
        O(1) in dataset size — the permutation is encoded as the
        pristine RNG state plus the number of shuffles to replay, so
        per-prefetch snapshots (PrefetchingIter) stay cheap."""
        state = {"cursor": int(self.cursor),
                 "rows": int(self.data[0][1].shape[0]),
                 "shuffles": int(self._shuffles)}
        if self._rng0 is not None:
            kind, keys, pos, has_gauss, cached = self._rng0
            state["rng0"] = [kind, [int(k) for k in keys], int(pos),
                             int(has_gauss), float(cached)]
        return state

    def load_state_dict(self, state):
        rows = int(self.data[0][1].shape[0])
        if int(state["rows"]) != rows:
            raise MXNetError(
                f"iterator state was saved over {state['rows']} samples; "
                f"this iterator holds {rows} — the resumed run must be "
                "constructed over the same data")
        if (state.get("rng0") is not None) != self._shuffle:
            raise MXNetError(
                "iterator state shuffle mode mismatch (saved "
                f"shuffle={state.get('rng0') is not None}, this iterator "
                f"shuffle={self._shuffle}); reconstruct the resumed "
                "iterator with the same shuffle setting or the batch "
                "sequence silently diverges")
        # rebuild the permutation exactly as __init__ + k-1 resets did:
        # full-arange shuffle, discard-truncation, then the later
        # shuffles over the truncated index
        idx = _np.arange(rows)
        nshuffles = int(state.get("shuffles", 0))
        if self._shuffle:
            kind, keys, pos, has_gauss, cached = state["rng0"]
            self._rng.set_state((kind,
                                 _np.asarray(keys, dtype=_np.uint32),
                                 int(pos), int(has_gauss), float(cached)))
            self._rng0 = self._rng.get_state()
            if nshuffles >= 1:
                self._rng.shuffle(idx)
        if self.last_batch_handle == "discard":
            idx = idx[:rows - rows % self.batch_size]
        if self._shuffle:
            for _ in range(nshuffles - 1):
                self._rng.shuffle(idx)
        self.idx = idx
        self._shuffles = nshuffles
        self.num_data = len(self.idx)
        self.cursor = int(state["cursor"])

    def iter_next(self):
        self.cursor += self.batch_size
        return self.cursor < self.num_data

    def _getdata(self, data_source):
        assert self.cursor < self.num_data, "DataIter needs reset."
        end = self.cursor + self.batch_size
        # unshuffled, idx is arange (discard only truncates it): a batch
        # that does not wrap past the end is a run of the cache's rows, and
        # a basic slice of a run is a view. Nothing is copied on the host
        # before the transfer; every other batch is gathered.
        run = not self._shuffle and end <= self.num_data
        if run:
            sel = slice(self.cursor, end)
        elif end <= self.num_data:
            sel = self.idx[self.cursor:end]
        else:
            sel = _np.concatenate([self.idx[self.cursor:],
                                   self.idx[:end - self.num_data]])
        with _profiler.span("input.slice"):
            rows = [self._np_cache[id(x)][sel] for _, x in data_source]
        _profiler.count("input.bytes", sum(r.nbytes for r in rows))
        if run:
            _profiler.count("input.views", len(rows))
        with _profiler.span("input.h2d"):
            return [nd_array(r) for r in rows]

    def getdata(self):
        return self._getdata(self.data)

    def getlabel(self):
        return self._getdata(self.label)

    def getpad(self):
        if self.last_batch_handle == "pad" and \
                self.cursor + self.batch_size > self.num_data:
            return self.cursor + self.batch_size - self.num_data
        return 0


class ResizeIter(DataIter):
    """Resize an iterator to a fixed number of batches per epoch
    (reference: io.py:277)."""

    def __init__(self, data_iter, size, reset_internal=True):
        super().__init__(data_iter.batch_size)
        self.data_iter = data_iter
        self.size = size
        self.reset_internal = reset_internal
        self.cur = 0
        self.current_batch = None
        self.provide_data = data_iter.provide_data
        self.provide_label = data_iter.provide_label
        if hasattr(data_iter, "default_bucket_key"):
            self.default_bucket_key = data_iter.default_bucket_key

    def reset(self):
        self.cur = 0
        if self.reset_internal:
            self.data_iter.reset()

    @property
    def supports_state(self):
        from .resilience.data import supports_state
        return supports_state(self.data_iter)

    def enable_state_snapshots(self):
        if hasattr(self.data_iter, "enable_state_snapshots"):
            self.data_iter.enable_state_snapshots()

    def state_dict(self):
        if not self.supports_state:
            raise MXNetError(
                f"wrapped iterator {type(self.data_iter).__name__} has no "
                "state_dict(); a ResizeIter snapshot would lose the data "
                "position")
        return {"cur": int(self.cur), "inner": self.data_iter.state_dict()}

    def load_state_dict(self, state):
        if state.get("inner") is None or not self.supports_state:
            raise MXNetError(
                "ResizeIter state carries no inner iterator position (or "
                "the wrapped iterator cannot restore one); refusing a "
                "resume that would silently replay the epoch head")
        self.cur = int(state["cur"])
        self.data_iter.load_state_dict(state["inner"])

    def iter_next(self):
        if self.cur == self.size:
            return False
        try:
            self.current_batch = self.data_iter.next()
        except StopIteration:
            self.data_iter.reset()
            self.current_batch = self.data_iter.next()
        self.cur += 1
        return True

    def getdata(self):
        return self.current_batch.data

    def getlabel(self):
        return self.current_batch.label

    def getindex(self):
        return self.current_batch.index

    def getpad(self):
        return self.current_batch.pad


class _ExchangeSlot:
    """Depth-1 producer/consumer hand-off (one prefetched batch).

    The producer must ``reserve()`` (wait for an empty slot) BEFORE
    touching its source and ``deposit()`` after — so whenever the slot
    is full the producer is parked in ``reserve`` and the source is
    quiescent. That ordering is what makes reset race-free: the
    consumer waits for a filled slot (``peek_filled``), resets the
    source while the producer is provably not reading it, and only then
    discards the stale item (``drain_and_let_refill``) to let the
    producer fetch from the freshly reset source.
    """

    _EMPTY = object()

    def __init__(self):
        self._cv = threading.Condition()
        self._item = self._EMPTY
        self.open = True

    def reserve(self):
        """Producer: wait until the slot can accept the NEXT item.

        Returns False when the slot was closed. Only after reserve()
        may the producer pull from its source."""
        with self._cv:
            while self._item is not self._EMPTY and self.open:
                self._cv.wait()
            return self.open

    def deposit(self, item):
        with self._cv:
            self._item = item
            self._cv.notify_all()

    def peek_filled(self):
        """Block until the slot holds something; leave it in place."""
        with self._cv:
            while self._item is self._EMPTY:
                self._cv.wait()
            return self._item

    def take(self):
        with self._cv:
            while self._item is self._EMPTY:
                self._cv.wait()
            item, self._item = self._item, self._EMPTY
            self._cv.notify_all()
            return item

    def drain_and_let_refill(self):
        """Discard whatever is staged and wake the producer."""
        with self._cv:
            while self._item is self._EMPTY:
                self._cv.wait()
            self._item = self._EMPTY
            self._cv.notify_all()

    def close(self):
        with self._cv:
            self.open = False
            self._cv.notify_all()


class _ProducerFailure:
    """An exception captured in a producer thread, staged through the
    exchange slot so the *consumer* re-raises it (a producer that just
    died would deadlock ``take()``)."""

    __slots__ = ("error",)

    def __init__(self, error):
        self.error = error


class _Staged:
    """What a producer deposits: the fetched item plus the source's
    state snapshot taken *before* the fetch. The pre-fetch snapshot is
    exactly the mid-epoch resume point for the staged-but-undelivered
    batch — restoring it makes the source produce that batch again, so
    prefetching never skips a batch across a checkpoint/resume."""

    __slots__ = ("pre_state", "item")

    def __init__(self, pre_state, item):
        self.pre_state = pre_state
        self.item = item


class PrefetchingIter(DataIter):
    """Thread-prefetching wrapper (reference: io.py:342 — the python analog
    of src/io/iter_prefetcher.h). One background thread per source stages
    the next batch into a depth-1 slot while the device computes on the
    current one; epoch end travels through the slot as ``None``."""

    def __init__(self, iters, rename_data=None, rename_label=None):
        super().__init__()
        self.iters = iters if isinstance(iters, list) else [iters]
        assert self.iters
        self.rename_data = rename_data
        self.rename_label = rename_label
        self.batch_size = self.provide_data[0][1][0]
        self.current_batch = None
        # pre-fetch state snapshots are off until armed: state_dict()
        # cost is source-defined (arbitrary user iterators may pay
        # O(dataset)), so paying it per prefetch is only justified when
        # checkpointing is on — fit() arms it via
        # enable_state_snapshots().
        # A plain dict (not `self`) is shared with the producer threads
        # so they hold no reference that would keep this object alive.
        self._snap_flag = {"on": False}
        # batch ordinals (profiler spans): the producers count the batches
        # they fetch and the consumer those it takes, each since the last
        # reset(), which zeroes both while the producers are parked
        self._fetched = [{"n": 0} for _ in self.iters]
        self._taken = 0
        self._slots = [_ExchangeSlot() for _ in self.iters]
        # the producers start fetching at once; no byte moves here
        with _profiler.span("input.construct", args={"bytes": 0}):
            for src, slot, fetched in zip(self.iters, self._slots,
                                          self._fetched):
                threading.Thread(target=self._produce,
                                 args=(src, slot, self._snap_flag, fetched),
                                 daemon=True).start()

    @staticmethod
    def _produce(source, slot, snap_flag, fetched):
        # per-prefetch snapshots only when armed AND the source can
        # snapshot all the way down (a wrapper over a snapshot-less
        # source *raises* from state_dict rather than losing the
        # position silently)
        from .resilience.data import supports_state
        can_snapshot = supports_state(source)
        while slot.reserve():  # False => closed
            pre_state = None
            try:
                if can_snapshot and snap_flag["on"]:
                    pre_state = source.state_dict()
                with _profiler.span("input.fetch", batch=fetched["n"]):
                    staged = source.next()
                fetched["n"] += 1
                _profiler.count("input.batches")
            except StopIteration:
                staged = None
            except BaseException as err:  # noqa: BLE001
                # A dying producer would leave the consumer parked in
                # take()/peek_filled() forever; ship the error through
                # the slot instead and stay alive for the next cycle
                # (reset() can still re-arm this source).
                staged = _ProducerFailure(err)
            slot.deposit(_Staged(pre_state, staged))

    def __del__(self):
        for slot in self._slots:
            slot.close()

    def _merged_descs(self, attr, renames):
        merged = []
        for k, src in enumerate(self.iters):
            mapping = renames[k] if renames is not None else None
            for d in getattr(src, attr):
                if isinstance(mapping, dict):
                    d = DataDesc(mapping[d.name], d.shape, d.dtype)
                merged.append(d)
        return merged

    @property
    def provide_data(self):
        return self._merged_descs("provide_data", self.rename_data)

    @property
    def provide_label(self):
        return self._merged_descs("provide_label", self.rename_label)

    def reset(self):
        # each producer is parked in put() while its slot is full, so the
        # sources are safe to reset; draining re-arms the producers on
        # the freshly reset sources
        for slot in self._slots:
            slot.peek_filled()
        for src in self.iters:
            src.reset()
        self._restart_ordinals()
        for slot in self._slots:
            slot.drain_and_let_refill()

    def _restart_ordinals(self):
        for fetched in self._fetched:
            fetched["n"] = 0
        self._taken = 0

    # -- checkpointable state (resilience/data.py, mid-epoch resume) ---------

    @property
    def supports_state(self):
        from .resilience.data import supports_state
        return all(supports_state(src) for src in self.iters)

    def enable_state_snapshots(self):
        """Arm per-prefetch state snapshots. Must be called before the
        batches that need checkpointing are prefetched — in practice,
        right after construction (fit() arms it automatically when a
        checkpoint destination is configured)."""
        self._snap_flag["on"] = True

    def state_dict(self):
        """Mid-epoch resume state. Waits for each producer to park
        (slot full → source quiescent) and returns the *pre-fetch*
        snapshot staged with the not-yet-delivered batch, so a restore
        re-produces exactly the batches the consumer has not seen."""
        if not self.supports_state:
            raise MXNetError(
                "a prefetched source has no state_dict(); a "
                "PrefetchingIter snapshot would lose its data position")
        if not self._snap_flag["on"]:
            raise MXNetError(
                "PrefetchingIter state snapshots are disarmed; call "
                "enable_state_snapshots() right after construction "
                "(fit() does this when checkpointing is configured)")
        states = []
        for slot in self._slots:
            staged = slot.peek_filled()
            if staged.pre_state is None:
                raise MXNetError(
                    "the staged batch was prefetched before "
                    "enable_state_snapshots(); arm snapshots before "
                    "iterating, then consume at least one batch")
            states.append(staged.pre_state)
        return {"inner": states}

    def load_state_dict(self, state):
        if not self.supports_state or any(s is None
                                          for s in state["inner"]):
            raise MXNetError(
                "PrefetchingIter state carries no position for some "
                "source; refusing a resume that would silently replay "
                "the epoch head")
        for slot in self._slots:    # park producers; sources quiescent
            slot.peek_filled()
        for src, inner in zip(self.iters, state["inner"]):
            src.load_state_dict(inner)
        self._restart_ordinals()
        for slot in self._slots:    # discard stale batch, refetch from
            slot.drain_and_let_refill()   # the restored position

    def iter_next(self):
        with _profiler.span("input.wait", batch=self._taken):
            staged = [slot.take().item for slot in self._slots]
        self._taken += 1
        for item in staged:
            if isinstance(item, _ProducerFailure):
                raise item.error
        if staged[0] is None:
            assert all(b is None for b in staged), \
                "Number of entry mismatches between iterators"
            return False
        assert len({b.pad for b in staged}) == 1, \
            "Different pad number in all iterators"
        data, label = [], []
        for b in staged:
            data.extend(b.data)
            label.extend(b.label or [])
        self.current_batch = DataBatch(
            data, label, staged[0].pad, staged[0].index,
            provide_data=self.provide_data,
            provide_label=self.provide_label)
        return True

    def next(self):
        if self.iter_next():
            return self.current_batch
        raise StopIteration

    def getdata(self):
        return self.current_batch.data

    def getlabel(self):
        return self.current_batch.label

    def getindex(self):
        return self.current_batch.index

    def getpad(self):
        return self.current_batch.pad


def _load_mnist_images(path):
    import gzip
    import struct
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        magic, num, rows, cols = struct.unpack(">IIII", f.read(16))
        if magic != 2051:
            raise MXNetError(f"bad MNIST image file {path}")
        data = _np.frombuffer(f.read(), dtype=_np.uint8)
        return data.reshape(num, rows, cols)


def _load_mnist_labels(path):
    import gzip
    import struct
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        magic, num = struct.unpack(">II", f.read(8))
        if magic != 2049:
            raise MXNetError(f"bad MNIST label file {path}")
        return _np.frombuffer(f.read(), dtype=_np.uint8)


def MNISTIter(image="train-images-idx3-ubyte", label="train-labels-idx1-ubyte",
              batch_size=128, shuffle=True, flat=False, silent=False,
              data_name="data", label_name="softmax_label", input_shape=None,
              **kwargs):
    """MNIST idx-format iterator (reference: src/io/iter_mnist.cc).

    Reads the standard idx(.gz) files and serves them through NDArrayIter.
    """
    import os
    for p in (image, label):
        if not os.path.exists(p):
            raise MXNetError(f"MNIST file not found: {p}")
    images = _load_mnist_images(image).astype(_np.float32) / 255.0
    labels = _load_mnist_labels(label).astype(_np.float32)
    if flat:
        images = images.reshape(len(images), -1)
    else:
        images = images.reshape(len(images), 1, 28, 28)
    if input_shape is not None:
        images = images.reshape((len(images),) + tuple(input_shape))
    return NDArrayIter(images, labels, batch_size=batch_size, shuffle=shuffle,
                       data_name=data_name, label_name=label_name)


def CSVIter(data_csv, data_shape, label_csv=None, label_shape=(1,),
            batch_size=128, round_batch=True, **kwargs):
    """CSV iterator (reference: src/io/iter_csv.cc)."""
    data = _np.loadtxt(data_csv, delimiter=",", dtype=_np.float32)
    data = data.reshape((-1,) + tuple(data_shape))
    label = None
    if label_csv is not None:
        label = _np.loadtxt(label_csv, delimiter=",", dtype=_np.float32)
        label = label.reshape((-1,) + tuple(label_shape))
        if label.shape[-1] == 1:
            label = label.reshape(label.shape[:-1])
    return NDArrayIter(data, label, batch_size=batch_size,
                       last_batch_handle="pad" if round_batch else "discard")


def LibSVMIter(data_libsvm, data_shape, label_shape=(1,), batch_size=128,
               round_batch=True, **kwargs):
    """LibSVM-format iterator yielding CSR data batches (reference:
    src/io/iter_libsvm.cc — 'label idx:val idx:val …' per line; feature
    indices are 0-based as in the reference's docs). Only scalar labels
    are supported (the reference's multi-label mode reads a second
    label_libsvm file; pass label_shape=(1,))."""
    from .ndarray import sparse as _sparse

    lw = 1
    for v in label_shape:
        lw *= int(v)
    if lw != 1:
        raise MXNetError(
            "LibSVMIter: only scalar labels are supported "
            "(label_shape=(1,)); multi-dim labels need a label_libsvm "
            "file, which is not implemented")
    num_features = 1
    for s in data_shape:
        num_features *= int(s)
    labels, indptr, indices, values = [], [0], [], []
    with open(data_libsvm) as fin:
        for line in fin:
            parts = line.split()
            if not parts:
                continue
            labels.append(float(parts[0]))
            for tok in parts[1:]:
                idx, _, val = tok.partition(":")
                indices.append(int(idx))
                values.append(float(val))
            indptr.append(len(indices))
    n = len(labels)
    label_arr = _np.asarray(labels, _np.float32)
    values = _np.asarray(values, _np.float32)
    indices = _np.asarray(indices, _np.int64)
    indptr = _np.asarray(indptr, _np.int64)

    class _LibSVMIter(DataIter):
        def __init__(self):
            super().__init__(batch_size)
            self.cur = 0

        @property
        def provide_data(self):
            return [DataDesc("data", (batch_size, num_features))]

        @property
        def provide_label(self):
            return [DataDesc("label", (batch_size,))]

        def reset(self):
            self.cur = 0

        def next(self):
            if self.cur >= n:
                raise StopIteration
            i0 = self.cur
            i1 = min(i0 + batch_size, n)
            pad = batch_size - (i1 - i0)
            if pad and not round_batch:
                raise StopIteration
            rows = list(range(i0, i1)) + [i0] * pad  # wrap-pad like the ref
            ptr = [0]
            ind, val = [], []
            lab = _np.zeros((batch_size,), _np.float32)
            for k, r in enumerate(rows):
                ind.extend(indices[indptr[r]:indptr[r + 1]])
                val.extend(values[indptr[r]:indptr[r + 1]])
                ptr.append(len(ind))
                lab[k] = label_arr[r]
            data = _sparse.csr_matrix(
                (_np.asarray(val, _np.float32),
                 _np.asarray(ind, _np.int64),
                 _np.asarray(ptr, _np.int64)),
                shape=(batch_size, num_features))
            self.cur = i1
            return DataBatch(data=[data], label=[nd_array(lab)], pad=pad,
                             provide_data=self.provide_data,
                             provide_label=self.provide_label)

    return _LibSVMIter()


def ImageRecordIter(*args, **kwargs):
    """C-registry alias: the image pipeline lives in mx.image (reference
    exposes ImageRecordIter under mx.io as well)."""
    from .image import ImageRecordIter as _iri
    return _iri(*args, **kwargs)


class MXDataIter(DataIter):
    """Wrapper type for backend-registered iterators (reference io.py:721
    wraps a C iterator handle). The rebuild's registered iterators
    (MNISTIter/CSVIter/LibSVMIter/ImageRecordIter) construct python-native
    DataIters directly, so this class exists for isinstance/import
    compatibility."""
