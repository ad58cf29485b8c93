# Native components of mxnet_tpu (reference analogue: the Makefile building
# libmxnet.so; here the native surface is the IO/runtime layer — the compute
# path is JAX/XLA).
#
#   make            build all native libs into mxnet_tpu/_lib/ (each linked
#                   under a temporary name and renamed: tests in several
#                   workers run `make` at once in a fresh checkout)
#   make clean

CXX      ?= g++
CXXFLAGS ?= -O2 -std=c++17 -fPIC -Wall -Wextra
LDFLAGS  ?= -shared -pthread

LIBDIR   := mxnet_tpu/_lib
IO_SRCS  := src/io/recordio.cc

PY_INCLUDES := $(shell python3-config --includes)
PY_LDFLAGS  := $(shell python3-config --ldflags) \
               -lpython$(shell python3 -c 'import sys; print("%d.%d" % sys.version_info[:2])')

all: $(LIBDIR)/libmxtpu_io.so $(LIBDIR)/libmxtpu_predict.so \
     $(LIBDIR)/libmxtpu.so

$(LIBDIR)/libmxtpu_io.so: $(IO_SRCS) src/io/mxtpu_io.h
	@mkdir -p $(LIBDIR)
	$(CXX) $(CXXFLAGS) $(IO_SRCS) $(LDFLAGS) -o $@.$$$$.tmp \
	    && mv -f $@.$$$$.tmp $@

# C predict ABI: embeds CPython and drives mxnet_tpu/c_predict.py
# (reference analogue: src/c_api/c_predict_api.cc in libmxnet.so)
$(LIBDIR)/libmxtpu_predict.so: src/capi/c_predict_api.cc \
                               src/capi/c_predict_api.h \
                               src/capi/embed_common.h
	@mkdir -p $(LIBDIR)
	$(CXX) $(CXXFLAGS) $(PY_INCLUDES) src/capi/c_predict_api.cc \
	    $(LDFLAGS) $(PY_LDFLAGS) -o $@.$$$$.tmp && mv -f $@.$$$$.tmp $@

# Training C ABI: NDArray/Symbol/Executor/KVStore core (c_api.h);
# embeds CPython and drives mxnet_tpu/c_api.py (reference analogue:
# src/c_api/{c_api.cc,c_api_ndarray.cc,c_api_symbolic.cc,...})
$(LIBDIR)/libmxtpu.so: src/capi/c_api.cc src/capi/c_api.h \
                       src/capi/embed_common.h
	@mkdir -p $(LIBDIR)
	$(CXX) $(CXXFLAGS) $(PY_INCLUDES) src/capi/c_api.cc \
	    $(LDFLAGS) $(PY_LDFLAGS) -o $@.$$$$.tmp && mv -f $@.$$$$.tmp $@

clean:
	rm -rf $(LIBDIR)

# ---------------------------------------------------------------------------
# CI matrix (reference analogue: Jenkinsfile:101-230 build/test stages).
# Each target is one gated stage; ci/pipeline.yml sequences them. Stages
# run on the virtual 8-device CPU mesh (tests/conftest.py) so the whole
# matrix is hermetic — no accelerator required.
# ---------------------------------------------------------------------------

# stage 0: tpu-lint — AST-based static analysis for TPU/JAX hazards
# (host syncs under trace, trace-time side effects, retrace storms,
# untracked RNG, registry/test/doc drift; docs/how_to/tpu_lint.md).
# Fails on findings not in the committed tpu-lint-baseline.json.
lint-tpu:
	python -m mxnet_tpu.analysis --root . mxnet_tpu

# the concurrency tier alone (lock-order cycles, unguarded shared
# state, check-then-act, cond-wakeup, signal safety over the threaded
# serving/resilience stack) — ZERO baseline: every finding here is a
# failure, readable in isolation via the --only filter.
# --no-baseline makes the stage itself enforce that: a concurrency
# finding snuck into tpu-lint-baseline.json still fails here.
lint-concurrency:
	python -m mxnet_tpu.analysis --root . --only concurrency \
	    --no-baseline mxnet_tpu

# the memory tier alone (use-after-donate, donation-alias-leak,
# unbounded-device-retention over the whole-program donation model) —
# same ZERO-baseline policy as the concurrency tier.
lint-memory:
	python -m mxnet_tpu.analysis --root . --only memory \
	    --no-baseline mxnet_tpu

ci-lint: lint-tpu lint-concurrency lint-memory

# stage 1: native shared libraries
ci-native: all

# stage 2: the amalgamation builds and loads standalone
ci-amalgamation: ci-native
	python amalgamation/amalgamation.py
	python -m pytest tests/test_amalgamation.py -x -q

# stage 3: unit suite (excludes the tiers owned by their own stages)
ci-unit: ci-native
	python -m pytest tests/ -x -q \
	    --ignore-glob='tests/test_examples_*.py' \
	    --ignore=tests/test_distributed.py \
	    --ignore=tests/test_perl_frontend.py \
	    --ignore=tests/test_amalgamation.py

# stage 4: every example executes with its asserts
ci-examples: ci-native
	python -m pytest tests/test_examples_*.py -x -q

# stage 5: real 2-process jax.distributed run
ci-distributed: ci-native
	python -m pytest tests/test_distributed.py -x -q

# stage 6: foreign frontends over the C ABI (C++ is part of ci-unit via
# test_c_api_train; perl builds its XS extension and trains)
ci-frontends: ci-native
	perl-package/AI-MXNetTPU/build.sh
	python -m pytest tests/test_perl_frontend.py -x -q

# stage 7: the driver contract (entry compile-check + multichip dryrun)
# MXTPU_MULTICHIP_FAST=1: the dry run's tracked-benchmark tail runs the
# CI smoke config (marked smoke, not a comparable round) — the full
# measurement belongs to the driver's MULTICHIP round / bench stage
ci-dryrun: ci-native
	MXTPU_MULTICHIP_FAST=1 JAX_PLATFORMS=cpu \
	    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
	    python -c "import __graft_entry__ as g; g.dryrun_multichip(8)"

# stage 8: fault-injection smoke — crash-safe checkpoints, auto-resume,
# retry/backoff under deterministic faults (docs/how_to/fault_tolerance.md)
ci-resilience: ci-native
	JAX_PLATFORMS=cpu python -m pytest tests/test_resilience.py \
	    -m 'not slow' -x -q

# stage 9: serving smoke — boot a threaded server on a toy model, arm a
# FaultPlan that kills the backend mid-stream, assert shed/open/recover
# without hangs (docs/how_to/serving.md); `timeout` bounds the stage so
# a reintroduced hang fails instead of wedging the runner
ci-serving: ci-native
	timeout -k 10 120 env JAX_PLATFORMS=cpu python ci/serving_smoke.py
	JAX_PLATFORMS=cpu python -m pytest tests/test_serving.py \
	    -m 'not slow' -x -q

# stage 9b: continuous-batching smoke — under MXTPU_RETRACE_STRICT=1,
# concurrent submitters coalesce into measurably fewer dispatches than
# requests, LSTM decode slots join/leave the running batch mid-flight
# with outputs bitwise-equal to sequential execution, and zero live
# compiles anywhere in the batched path (docs/how_to/serving.md)
ci-batching: ci-native
	timeout -k 10 180 env JAX_PLATFORMS=cpu MXTPU_RETRACE_STRICT=1 \
	    python ci/batching_smoke.py
	JAX_PLATFORMS=cpu python -m pytest tests/test_batching.py \
	    -m 'not slow' -x -q

# stage 9c: ragged-serving smoke — under MXTPU_RETRACE_STRICT=1, a
# mixed-length burst packs into shared rows with bitwise scatter and a
# sub-dense pad-waste token ratio, a symbolic-dim backend serves every
# batch size through ONE warmed signature (warm-up matrix collapsed),
# the masked decode step is bitwise vs dense across join/leave, and
# MXTPU_RAGGED=0 hands the backend the exact dense feed
# (docs/how_to/serving.md "Ragged & packed batching")
ci-ragged: ci-native
	timeout -k 10 180 env JAX_PLATFORMS=cpu MXTPU_RETRACE_STRICT=1 \
	    python ci/ragged_smoke.py
	JAX_PLATFORMS=cpu python -m pytest tests/test_ragged.py \
	    -m 'not slow' -x -q

# stage 10: data-pipeline chaos smoke — a short fit over deliberately
# corrupted .rec shards with MXNET_TPU_FAULT_PLAN arming the io.open_shard/
# io.read_record sites: the run must complete within the skip budget,
# stats must report the injected faults, and a kill + fit(resume='auto')
# must reproduce the exact batch sequence
# (docs/how_to/data_resilience.md)
ci-data: ci-native
	timeout -k 10 180 env JAX_PLATFORMS=cpu \
	    MXNET_TPU_FAULT_PLAN="io.open_shard:2:ioerror;io.read_record:5:ioerror" \
	    python ci/data_chaos_smoke.py
	JAX_PLATFORMS=cpu python -m pytest tests/test_resilience_data.py \
	    -m 'not slow' -x -q

# stage 11: step-runtime smoke — a 2-step micro-LSTM and micro-attention
# through the fused runtime (mxnet_tpu/perf) asserting no-retrace
# (MXTPU_RETRACE_STRICT=1) and bitwise donation-equivalence
# (docs/how_to/performance.md); CPU-only, inside the tier-1 time budget
ci-perf: ci-native
	timeout -k 10 120 env JAX_PLATFORMS=cpu python ci/perf_smoke.py
	JAX_PLATFORMS=cpu python -m pytest tests/test_perf_runtime.py \
	    -m 'not slow' -x -q

# stage 12: elastic chaos smoke — the 8-device CPU mesh with
# MXNET_TPU_FAULT_PLAN killing a device at a seeded probe: detect →
# checkpoint → re-mesh (8→4 past the batch-divisibility wall) →
# re-shard → resume with the bitwise-identical batch stream and
# allclose losses vs an uninterrupted run; plus the mid-step collective
# death (restore + rewind). Injectable clocks only; `timeout` bounds
# the stage so a reintroduced hang fails instead of wedging the runner
# (docs/how_to/elastic_training.md)
ci-elastic: ci-native
	timeout -k 10 300 env JAX_PLATFORMS=cpu \
	    MXNET_TPU_FAULT_PLAN="mesh.probe:4:ioerror" \
	    MXNET_TPU_FAULT_SEED=7 \
	    python ci/elastic_chaos_smoke.py
	JAX_PLATFORMS=cpu python -m pytest tests/test_elastic.py \
	    -m 'not slow' -x -q

# stage 13: compiler smoke — two cold→warm runs of a micro model against
# a fresh cache dir (under MXTPU_RETRACE_STRICT=1): the warm process must
# record cache hits + a compile-count drop + a faster start, a corrupt
# entry must cost exactly one recompile, and the pass-correctness suite
# (bitwise equivalence vs un-passed graphs) must hold
# (docs/how_to/compiler.md)
ci-compiler: ci-native
	timeout -k 10 420 env JAX_PLATFORMS=cpu python ci/compiler_smoke.py
	JAX_PLATFORMS=cpu python -m pytest tests/test_compiler.py \
	    -m 'not slow' -x -q

# stage 14: preemption chaos smoke — a REAL SIGTERM to a child training
# process mid-epoch must yield the typed exit code, the clean-exit
# marker and a bitwise-exact resumed batch stream; a second leg injects
# a step stall via MXNET_TPU_FAULT_PLAN and the escalation ladder
# (retry → rebind) must recover unattended; then the unit suite
# (signals, watchdog, crash-loop — fake clocks, zero sleeps)
# (docs/how_to/preemption.md)
ci-preempt: ci-native
	timeout -k 10 300 env JAX_PLATFORMS=cpu python ci/preempt_smoke.py
	JAX_PLATFORMS=cpu python -m pytest tests/test_supervisor.py \
	    -m 'not slow' -x -q

# stage 15: multichip smoke — the 8-virtual-device CPU mesh under
# MXTPU_RETRACE_STRICT=1: the ZeRO-sharded step must reproduce the
# replicated step (losses allclose, params bitwise), the compiled ZeRO
# HLO must carry an actual all-gather (the updated-param re-gather is
# inside the donated program, not per-step host traffic), the measured
# optimizer-state bytes/chip must drop by the data degree, and zero
# retraces; then the rule-engine/ZeRO unit suite
# (docs/how_to/multichip.md)
ci-multichip: ci-native
	timeout -k 10 300 env JAX_PLATFORMS=cpu \
	    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
	    MXTPU_RETRACE_STRICT=1 \
	    python ci/multichip_smoke.py
	JAX_PLATFORMS=cpu python -m pytest tests/test_sharding_rules.py \
	    -m 'not slow' -x -q

# stage 16: fleet chaos smoke — a REAL threaded 3-replica fleet under
# MXNET_TPU_FAULT_PLAN (fleet.dispatch kills one replica mid-burst:
# zero lost requests, eviction + standby failover observable, chaos p99
# within the stated bound of a no-fault reference) plus one rolling
# v1->v2 reload with zero dropped requests and the rollback gate
# enforced — all under MXTPU_RETRACE_STRICT=1, so finishing clean is
# the zero-retrace assertion; then the fake-clock unit suite
# (docs/how_to/fleet.md)
ci-fleet: ci-native
	timeout -k 10 180 env JAX_PLATFORMS=cpu MXTPU_RETRACE_STRICT=1 \
	    MXNET_TPU_FAULT_PLAN="fleet.dispatch:10:ioerror" \
	    MXNET_TPU_FAULT_SEED=7 \
	    python ci/fleet_smoke.py
	JAX_PLATFORMS=cpu python -m pytest tests/test_fleet.py \
	    -m 'not slow' -x -q

# stage 17: low-precision smoke — calibrate + quantize a micro ResNet
# and a micro LSTM (sidecar snapshot + reload without recalibration),
# serve both coalesced through the InferenceServer under
# MXTPU_RETRACE_STRICT=1 (finishing clean IS the zero-retrace
# assertion) with accuracy delta <= the gate and zero unwarmed int8
# signatures, quant-vs-fp32 persistent program keys distinct, the
# gate's refusal leg (typed warning + fp32 fallback), and a bf16-mode
# poison step skipped bitwise; then the unit suite
# (docs/how_to/quantization.md)
ci-quant: ci-native
	timeout -k 10 420 env JAX_PLATFORMS=cpu MXTPU_RETRACE_STRICT=1 \
	    python ci/quant_smoke.py
	JAX_PLATFORMS=cpu python -m pytest tests/test_quant.py -x -q

# stage 18: checkpoint kill-matrix chaos smoke — an InjectedKill at
# every async/sharded checkpoint fault site (snapshot, per-shard write,
# manifest commit, flush barrier, stale sweep, crash-loop resume
# counter) must leave discovery loading only complete committed
# checkpoints; a 4-way sharded checkpoint must restore bitwise onto 2
# and 8; async fit must match sync fit bitwise and resume; then the
# async/sharded unit suite (docs/how_to/fault_tolerance.md,
# "Async & sharded checkpoints")
ci-checkpoint: ci-native
	timeout -k 10 300 env JAX_PLATFORMS=cpu python ci/ckpt_chaos.py
	JAX_PLATFORMS=cpu python -m pytest tests/test_async_checkpoint.py \
	    -m 'not slow' -x -q

# silent-corruption chaos: a seeded lying-chip bitflip (nothing raises)
# must be voted out by the cross-replica checksum within one period and
# the run must resume exactly; a transient sentinel breach must
# rollback-and-replay clean — both under MXTPU_RETRACE_STRICT=1 (the
# sentinel riding the donated step state must never cost a retrace);
# then the integrity unit suite (docs/how_to/integrity.md)
ci-integrity: ci-native
	timeout -k 10 300 env JAX_PLATFORMS=cpu \
	    MXNET_TPU_FAULT_PLAN="mesh.silent_corrupt:4:ioerror" \
	    MXNET_TPU_FAULT_SEED=7 \
	    python ci/integrity_smoke.py
	JAX_PLATFORMS=cpu python -m pytest tests/test_integrity.py \
	    -m 'not slow' -x -q

# stage 21: gray-failure / straggler chaos — serve leg: a threaded
# 3-replica fleet with one replica made sticky-slow by an env-armed
# `delay` fault must lose zero requests, hedge around the straggler,
# vote it out on the latency rung and hold the p99 bound, all under
# MXTPU_RETRACE_STRICT=1; train leg: a persistently slow step walks
# the supervisor's slow ladder into a DEGRADED quarantine + unattended
# elastic re-mesh; then the deterministic fake-clock unit suite
# (docs/how_to/fleet.md "Gray failure & hedging")
ci-straggler: ci-native
	timeout -k 10 180 env JAX_PLATFORMS=cpu MXTPU_RETRACE_STRICT=1 \
	    MXNET_TPU_FAULT_PLAN="fleet.dispatch:10:delay:400" \
	    MXNET_TPU_FAULT_SEED=7 \
	    python ci/straggler_smoke.py serve
	timeout -k 10 300 env JAX_PLATFORMS=cpu \
	    python ci/straggler_smoke.py train
	JAX_PLATFORMS=cpu python -m pytest tests/test_straggler.py \
	    -m 'not slow' -x -q

ci: ci-lint ci-native ci-amalgamation ci-unit ci-examples ci-distributed \
    ci-frontends ci-dryrun ci-resilience ci-serving ci-batching ci-ragged \
    ci-data ci-perf ci-elastic ci-compiler ci-preempt ci-multichip \
    ci-fleet ci-quant ci-checkpoint ci-integrity ci-straggler
	@echo "CI matrix green"

.PHONY: all clean ci lint-tpu lint-concurrency lint-memory ci-lint ci-native \
	ci-amalgamation ci-unit \
        ci-examples ci-distributed ci-frontends ci-dryrun ci-resilience \
        ci-serving ci-batching ci-ragged ci-data ci-perf ci-elastic \
        ci-compiler ci-preempt ci-multichip ci-fleet ci-quant \
        ci-checkpoint ci-integrity ci-straggler
