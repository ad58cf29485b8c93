#!/usr/bin/env python
"""CI smoke for the compiler layer (make ci-compiler).

The acceptance contract of the graph-pass + persistent-cache subsystem
(docs/how_to/compiler.md), asserted end to end with REAL processes:

1. two cold->warm runs of a micro model against a fresh cache dir
   (benchmarks/bench_compile_cache.py children, MXTPU_RETRACE_STRICT=1):
   the second process must record cache hits, load every program it
   needs, compile NOTHING, and come up measurably faster;
2. a corrupt cache entry must cost exactly one recompile — never a
   failure (the ``compiler.cache.read`` resilience contract);
3. pass-transformed programs are bitwise-identical to un-passed ones
   (the full equivalence suite runs in the pytest half of the stage).

Exit 0 = green. Any assertion failure or child crash fails the stage.
"""
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

import bench_compile_cache  # noqa: E402


def main():
    tmp = bench_compile_cache.empty_cache_dir()
    try:
        print("== cold run (empty cache) ==", flush=True)
        cold = bench_compile_cache.run_child(tmp)
        cstats = cold["stats"]
        print(f"cold: ready={cold['ready_s']:.3f}s "
              f"compiled={cstats['programs']['compiled']} "
              f"hits={cstats['cache']['hits']} "
              f"writes={cstats['cache']['writes']}", flush=True)
        assert cstats["cache"]["hits"] == 0, "cold run must not hit"
        assert cstats["programs"]["compiled"] >= 2, \
            "cold run must compile the fwd + fwd_bwd programs"
        assert cstats["cache"]["writes"] >= 2, \
            "cold run must persist its executables"

        print("== warm run (same model, fresh process) ==", flush=True)
        warm = bench_compile_cache.run_child(tmp)
        wstats = warm["stats"]
        print(f"warm: ready={warm['ready_s']:.3f}s "
              f"compiled={wstats['programs']['compiled']} "
              f"loaded={wstats['programs']['loaded']} "
              f"hits={wstats['cache']['hits']}", flush=True)
        assert wstats["cache"]["hits"] >= 1, \
            "warm run recorded no cache hit"
        assert wstats["programs"]["loaded"] >= 2, \
            "warm run must deserialize its programs"
        assert wstats["programs"]["compiled"] < \
            cstats["programs"]["compiled"], \
            "warm run must compile strictly less than the cold run"
        assert warm["ready_s"] < cold["ready_s"], (
            f"cache_warm_start_s ({warm['ready_s']:.3f}) must beat "
            f"compile_cold_start_s ({cold['ready_s']:.3f})")

        print("== corrupt-entry fallback ==", flush=True)
        # flip a byte in every stored executable: the third run must
        # quarantine + recompile, never fail
        flipped = 0
        for dirpath, _dirs, names in os.walk(tmp):
            for name in names:
                if name.endswith(".bin"):
                    path = os.path.join(dirpath, name)
                    with open(path, "r+b") as f:
                        f.seek(16)
                        f.write(b"\xff\xff\xff\xff")
                    flipped += 1
        assert flipped >= 2, "expected persisted executables to corrupt"
        rerun = bench_compile_cache.run_child(tmp)
        rstats = rerun["stats"]
        print(f"post-corruption: compiled={rstats['programs']['compiled']} "
              f"invalidations={rstats['cache']['invalidations']}",
              flush=True)
        assert rstats["cache"]["invalidations"] >= 1, \
            "corrupt entries must be detected and quarantined"
        assert rstats["programs"]["compiled"] >= 2, \
            "corrupt entries must fall back to recompile"

        speedup = cold["ready_s"] / max(warm["ready_s"], 1e-9)
        print(f"ci-compiler smoke green: compile_cold_start_s="
              f"{cold['ready_s']:.3f} cache_warm_start_s="
              f"{warm['ready_s']:.3f} ({speedup:.2f}x)", flush=True)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


if __name__ == "__main__":
    main()
