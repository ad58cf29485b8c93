#!/usr/bin/env python
"""Distributed job launcher.

Reference surface: tools/launch.py (dmlc-core tracker, --launcher
local/ssh/mpi/..., spawning scheduler + servers + workers with DMLC_* env
— SURVEY.md §3.5). TPU-native: there are no server/scheduler roles — one
SPMD process per host joins a jax.distributed process group. This tool
covers the ``local`` launcher (N processes on this machine, the mode the
reference's nightly dist tests use); for real clusters, run the same
command per host with MXTPU_PROC_ID set by your scheduler (SLURM/k8s), or
rely on jax's native cloud auto-detection.

    python tools/launch.py -n 4 python my_training_script.py

Each process must call mxnet_tpu.parallel.dist.init_process_group().

One process drives all the local chips of a host, and a chip belongs to
one process at a time: ``-n N`` with N > 1 on ONE host is the CPU mode
(``JAX_PLATFORMS=cpu``). On a host with accelerators every local worker
would try to open the same chips, and all but the first fail or hang.
"""
import argparse
import os
import socket
import subprocess
import sys


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def main():
    ap = argparse.ArgumentParser(
        description="launch a multi-process mxnet_tpu job")
    ap.add_argument("-n", "--num-workers", type=int, required=True,
                    help="number of worker processes")
    ap.add_argument("-s", "--num-servers", type=int, default=0,
                    help="accepted for reference-CLI parity; ignored "
                         "(there are no server processes in SPMD)")
    ap.add_argument("--launcher", default="local", choices=["local"],
                    help="only 'local' spawns here; for ssh/mpi/slurm set "
                         "MXTPU_* env per host instead")
    ap.add_argument("--env", action="append", default=[],
                    help="extra KEY=VALUE env for every worker")
    ap.add_argument("command", nargs=argparse.REMAINDER)
    args = ap.parse_args()
    if args.command and args.command[0] == "--":
        args.command = args.command[1:]
    if not args.command:
        ap.error("no command given")
    if args.num_servers:
        print("note: -s/--num-servers ignored — SPMD collectives replace "
              "parameter servers", file=sys.stderr)

    coordinator = f"127.0.0.1:{_free_port()}"
    procs = []
    for i in range(args.num_workers):
        env = dict(os.environ)
        env["MXTPU_COORDINATOR"] = coordinator
        env["MXTPU_NUM_PROCS"] = str(args.num_workers)
        env["MXTPU_PROC_ID"] = str(i)
        for kv in args.env:
            k, _, v = kv.partition("=")
            env[k] = v
        procs.append(subprocess.Popen(args.command, env=env))

    # poll rather than wait sequentially: when one worker dies, the rest
    # may be blocked in a collective waiting for it — tear them down
    import time
    rc = 0
    while True:
        codes = [p.poll() for p in procs]
        failed = [c for c in codes if c not in (None, 0)]
        if failed:
            rc = failed[0]
            for p in procs:
                if p.poll() is None:
                    p.terminate()
            break
        if all(c is not None for c in codes):
            break
        time.sleep(0.2)
    for p in procs:
        try:
            p.wait(timeout=10)
        except subprocess.TimeoutExpired:
            p.kill()
    sys.exit(rc)


if __name__ == "__main__":
    main()
