"""Ragged-serving smoke stage (`make ci-ragged`, docs/how_to/serving.md
"Ragged & packed batching").

Runs under ``MXTPU_RETRACE_STRICT=1`` — a single live-request compile
anywhere in the ragged path fails the stage — and asserts the pad-tax
contracts end to end:

1. **sequence packing**: a mixed-length burst against a packed server
   packs several short requests per padded row; every member's result
   is BITWISE equal to running it alone, the pad-waste token ratio is
   measurably below what dense padding would have burned, and zero
   dispatch signatures fall outside the warmed set;
2. **symbolic-dim programs**: a ``SymbolicJitBackend`` server warms ONE
   probe where the dense matrix would take ``len(coalescer_sizes)``
   (reported as ``warmup_skipped_covered``), then serves every batch
   size in the burst through that one warmed symbolic signature;
3. **masked decode**: an ``InflightBatcher`` whose backend consumes the
   fed-slot mask decodes join/leave-mid-stream schedules bitwise equal
   to the unmasked batcher, with the decode pad tax tracked;
4. **kill switch**: ``ragged=False`` hands the backend exactly the
   dense feed (no mask, no segment plane) — today's path, bitwise.

The whole script is bounded by `timeout` in the Makefile, so a
regression that reintroduces a hang fails the stage instead of wedging
the runner.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np

from mxnet_tpu.serving import (CallableBackend, CallableStepBackend,  # noqa: E402
                               InferenceServer, InflightBatcher,
                               SymbolicJitBackend)

BUCKET = 16
MAX_BATCH = 8


def smoke_packing():
    def fn(arrays):
        assert "segment_ids" in arrays, "packed dispatch lost its plane"
        return [np.asarray(arrays["data"], np.float32) * 3.0 + 1.0]

    server = InferenceServer(
        CallableBackend(fn, input_specs={"data": (BUCKET, 4)},
                        pack_axis=1, accepts_segment_ids=True),
        name="ragged-smoke-packed", max_batch=MAX_BATCH, workers=0,
        default_deadline=30.0)
    server.warm_up()
    lengths = [3, 5, 2, 7, 1, 4, 6, 2, 3, 5, 1, 2]
    arrays = [(np.arange(n * 4, dtype=np.float32).reshape(1, n, 4)
               + 100.0 * i) for i, n in enumerate(lengths)]
    reqs = [server.submit({"data": a}) for a in arrays]
    server.run_pending()
    for arr, req in zip(arrays, reqs):
        got = server.result(req)
        np.testing.assert_array_equal(got[0], arr * 3.0 + 1.0)
    st = server.stats()
    pw = st["pad_waste"]
    dense_tokens = len(lengths) * BUCKET   # one padded row per request
    assert st["packed_dispatches"] >= 1, st
    assert st["batching"]["unwarmed_dispatch_signatures"] == 0, st
    assert pw["real_tokens"] == sum(lengths), pw
    assert pw["padded_tokens"] < dense_tokens, (pw, dense_tokens)
    server.close()
    print(f"[ragged-smoke] packing: {len(lengths)} requests -> "
          f"{st['dispatches']} dispatches, token ratio "
          f"{pw['ratio']} (dense would be "
          f"{round(dense_tokens / pw['real_tokens'], 2)})")


def smoke_symbolic():
    server = InferenceServer(
        SymbolicJitBackend(lambda arrays: [arrays["data"] * 2.0],
                           max_rows=MAX_BATCH,
                           input_specs={"data": (4,)}),
        name="ragged-smoke-symbolic", max_batch=MAX_BATCH, workers=0,
        default_deadline=30.0)
    server.warm_up()
    st = server.stats()
    assert st["warmed_buckets"] == 1, st
    assert st["warmup_skipped_covered"] == 3, st       # sizes 1,2,4 skipped
    assert st["batching"]["warmed_signatures"] == 1, st
    sizes = (1, 3, 5, 2, 8, 7)
    reqs = [server.submit({"data": np.full((rows, 4), float(rows),
                                           np.float32)})
            for rows in sizes]
    server.run_pending()
    for rows, req in zip(sizes, reqs):
        np.testing.assert_array_equal(
            server.result(req)[0], np.full((rows, 4), rows * 2.0))
    st = server.stats()
    assert st["batching"]["unwarmed_dispatch_signatures"] == 0, st
    assert st["pad_waste"]["rows_ratio"] == 1.0, st    # no batch padding
    server.close()
    print(f"[ragged-smoke] symbolic: 1 warm probe covered "
          f"{st['warmup_skipped_covered']} dense sizes; "
          f"{len(sizes)}-size burst, 1 warmed signature, 0 unwarmed")


def smoke_masked_decode():
    def dense_step(inputs, states):
        h = np.tanh(states["h"] + inputs["x"])
        return [h * 2.0], {"h": h}

    def masked_step(inputs, states, mask=None):
        outs, nxt = dense_step(inputs, states)
        if mask is not None:
            outs = [o * mask[:, None] for o in outs]
            nxt = {k: v * mask[:, None] for k, v in nxt.items()}
        return outs, nxt

    specs = ({"x": (3,)}, {"h": (3,)})

    def drive(batcher):
        outs = []
        a = batcher.join()
        b = batcher.join()
        xa = np.full((3,), 0.5, np.float32)
        xb = np.full((3,), -0.25, np.float32)
        r = batcher.step({a: {"x": xa}, b: {"x": xb}})
        outs += [r[a][0], r[b][0]]
        c = batcher.join()
        r = batcher.step({a: {"x": xa}, c: {"x": xb}})
        outs += [r[a][0], r[c][0]]
        batcher.leave(b)
        r = batcher.step({c: {"x": xa}})
        outs.append(r[c][0])
        return outs

    dense = InflightBatcher(CallableStepBackend(dense_step, *specs),
                            capacity=4, name="ragged-smoke-dense",
                            ragged=False).warm_up()
    masked = InflightBatcher(
        CallableStepBackend(masked_step, *specs, accepts_mask=True),
        capacity=4, name="ragged-smoke-masked", ragged=True).warm_up()
    for got_d, got_m in zip(drive(dense), drive(masked)):
        np.testing.assert_array_equal(got_d, got_m)
    st = masked.stats()
    assert st["masked"] and st["retraced"] == 0, st
    assert st["pad_waste"]["dispatches"] == 3, st
    print(f"[ragged-smoke] masked decode: bitwise vs dense across "
          f"join/leave, decode rows_ratio "
          f"{st['pad_waste']['rows_ratio']}")


def smoke_kill_switch():
    feeds = []

    def fn(arrays):
        feeds.append(sorted(arrays))
        return [np.asarray(arrays["data"], np.float32) * 2.0]

    server = InferenceServer(
        CallableBackend(fn, input_specs={"data": (4,)},
                        accepts_mask=True, pack_axis=1,
                        accepts_segment_ids=True),
        name="ragged-smoke-killed", max_batch=4, workers=0,
        ragged=False, default_deadline=30.0)
    server.warm_up()
    data = np.ones((3, 4), np.float32)
    req = server.submit({"data": data})
    server.run_pending()
    np.testing.assert_array_equal(server.result(req)[0], data * 2.0)
    assert all(names == ["data"] for names in feeds), feeds
    st = server.stats()["ragged"]
    assert not (st["enabled"] or st["packing"] or st["symbolic"]), st
    server.close()
    print("[ragged-smoke] kill switch: backend saw the exact dense "
          "feed (no mask, no segment plane)")


if __name__ == "__main__":
    assert os.environ.get("MXTPU_RETRACE_STRICT") == "1", \
        "stage contract: run under MXTPU_RETRACE_STRICT=1"
    smoke_packing()
    smoke_symbolic()
    smoke_masked_decode()
    smoke_kill_switch()
    print("[ragged-smoke] OK")
