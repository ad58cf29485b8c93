"""Checkpoint-stall benchmark: what does the step loop PAY per
checkpoint, sync vs async?

Sync leg: the full blocking write a reference-style fit pays on the
training thread — serialize + atomic tmp/fsync/rename + SHA-256
manifest commit (``write_sharded_checkpoint``, one shard: the same
commit machinery the async writer uses).

Async leg: the snapshot-then-persist hiccup — host snapshot
(``snapshot_tree``) + ``AsyncCheckpointer.submit``; the commit runs on
the background writer, drained between samples so every sample
measures a steady-state submit (no back-pressure wait).

The value is the ratio ``sync_write_ms / async_hiccup_ms``
(bigger = the async path hides more of the write). The ACCEPTANCE
contract (enforced absolutely in bench.py) is
``async_hiccup < 0.1 * sync_write``: the step loop's checkpoint stall
drops by >= 10x (docs/how_to/fault_tolerance.md).
"""
import json
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

SYNC_ITERS = 5
ASYNC_ITERS = 8
WARMUP = 1


def _tree(total_mb):
    """A flat param-like tree of ``total_mb`` MB across mixed shapes."""
    rng = np.random.RandomState(0)
    n_floats = int(total_mb * (1 << 20) / 4)
    big = n_floats * 3 // 4
    rest = n_floats - big
    return {"arg:embed": rng.randn(big // 256, 256).astype(np.float32),
            "arg:w": rng.randn(rest // 128, 128).astype(np.float32),
            "state:step": np.int64(0)}


def run(quiet=False):
    from mxnet_tpu.resilience import AsyncCheckpointer
    from mxnet_tpu.resilience.async_checkpoint import (
        snapshot_tree, write_sharded_checkpoint)

    total_mb = float(os.environ.get("BENCH_CKPT_MB", "64"))
    tree = _tree(total_mb)

    with tempfile.TemporaryDirectory() as tmp:
        sprefix = os.path.join(tmp, "sync")
        # sync leg: the blocking write on the "training" thread
        for i in range(WARMUP):
            write_sharded_checkpoint(sprefix, i + 1, tree, num_shards=1)
        sync_times = []
        for i in range(SYNC_ITERS):
            t0 = time.perf_counter()
            write_sharded_checkpoint(sprefix, WARMUP + 1 + i, tree,
                                     num_shards=1)
            sync_times.append(time.perf_counter() - t0)

        # async leg: snapshot + submit is ALL the step loop pays
        aprefix = os.path.join(tmp, "async")
        ck = AsyncCheckpointer(name="bench-ckpt")
        hiccups = []
        for i in range(WARMUP + ASYNC_ITERS):
            epoch = i + 1
            t0 = time.perf_counter()
            snap = snapshot_tree(tree)
            ck.submit(epoch,
                      lambda _e=epoch, _s=snap: write_sharded_checkpoint(
                          aprefix, _e, _s, num_shards=1))
            dt = time.perf_counter() - t0
            if i >= WARMUP:
                hiccups.append(dt)
            ck.flush()          # drain outside the timed window
        ck.close()

    sync_ms = 1e3 * float(np.mean(sync_times))
    hiccup_ms = 1e3 * float(np.mean(hiccups))
    record = {
        "metric": "ckpt_stall",
        "value": round(sync_ms / hiccup_ms, 2),
        "unit": "x (sync blocking write / async step hiccup)",
        "sync_write_ms": round(sync_ms, 2),
        "async_hiccup_ms": round(hiccup_ms, 2),
        "hiccup_fraction": round(hiccup_ms / sync_ms, 4),
        "contract_hiccup_lt_0p1_sync": bool(hiccup_ms < 0.1 * sync_ms),
        "config": {"params_mb": total_mb, "sync_iters": SYNC_ITERS,
                   "async_iters": ASYNC_ITERS},
    }
    if not quiet:
        print(json.dumps(record))
    return record


if __name__ == "__main__":
    run()
