PYTHON ?= python

.PHONY: proto test bench native obs-check qos-check profile-check cache-check perf-check disagg-check spec-check chunk-check forensics-check lora-check tiers-check pack-check chaos-check fleet-check scale-check meter-check graph-check lint-check clean

proto:
	protoc --proto_path=seldon_core_tpu/proto \
	       --python_out=seldon_core_tpu/proto \
	       seldon_core_tpu/proto/prediction.proto

# no -march=native: the tree is copied to other hosts (the chip tool), and a
# binary built for this CPU could fault there.  NATIVE_OUT lets the tests
# build into a temp dir instead of leaving a binary in the package.
NATIVE_OUT ?= seldon_core_tpu/_native

native:
	mkdir -p $(NATIVE_OUT)
	g++ -O3 -shared -fPIC -o $(NATIVE_OUT)/libsctcodec.so csrc/codec.cpp

# tier-1, as the PR check selects and spreads it (ROADMAP.md "Tier-1 verify")
test:
	$(PYTHON) -m pytest tests/ -q -m 'not slow' -p xdist -n 6 --dist loadfile

bench:
	$(PYTHON) bench.py

# fast observability smoke: stub engine, 50 requests, asserts the new
# /prometheus histograms exist and /stats/breakdown accounts for the
# measured wall time (same test runs in tier-1)
obs-check:
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/test_obs.py -q -k obs_check

# overload acceptance gate (docs/QOS.md): saturating two-wave load, QoS-on
# sheds with sub-step 429s, spends zero device steps on shed requests, and
# beats QoS-off on completions-within-deadline (same test runs in tier-1)
qos-check:
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/test_qos.py -q -k qos_check

# perf-attribution plane gate: wire byte counters + /stats/wire shape +
# profiler start/stop lifecycle + always-on probes, then a smoke of the
# loopback big-payload bench control (device-free, CPU-safe)
profile-check:
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/test_obs.py -q \
		-k "WireAccounting or ProfilerLifecycle or AlwaysOnProbes"
	JAX_PLATFORMS=cpu BENCH_ONLY=loopback BENCH_SECONDS=1 BENCH_RUNS=2 \
		BENCH_LOOPBACK_ROWS=32 $(PYTHON) bench.py

# caching & reuse plane gate (docs/CACHING.md): cache/collapse/prefix unit
# + integration tests (zero-device-step hits, pinned-equal prefix reuse,
# spec-hash invalidation), then a CPU smoke of the bench cache stage
# (device-free stub graph: hit-rate sweep + collapsed herd)
cache-check:
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/test_cache.py -q
	JAX_PLATFORMS=cpu BENCH_ONLY=cache BENCH_SECONDS=2 \
		BENCH_CACHE_GRAPH=stub BENCH_CACHE_LLM=0 $(PYTHON) bench.py

# hot-path perf gate (docs/PERFORMANCE.md), CPU-safe: overlap smoke
# asserting ZERO per-token host syncs in steady-state decode (one fetch per
# fused block), the /stats/warmup attribution endpoint, and the warm-start
# p99 bound on the stub graph (same tests run in tier-1)
perf-check:
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/test_perf.py -q

# disaggregated prefill/decode gate (docs/DISAGGREGATION.md), CPU-safe:
# role-typed two-engine handoff on the stub mesh, pinned-equal
# disagg-vs-unified generation, zero-leak handoff failure, the routing
# policy bars (>=90% warm-replica prefix affinity, p2c skew <= 1.5x), then
# a smoke of the disagg bench stage (unified vs split TTFT under flood)
disagg-check:
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/test_disagg.py -q
	JAX_PLATFORMS=cpu BENCH_ONLY=disagg BENCH_SECONDS=2 BENCH_RUNS=1 \
		$(PYTHON) bench.py

# device-side decode frontier gate (docs/PERFORMANCE.md), CPU-safe:
# pinned-equal greedy spec-on == spec-off (incl. overlap, prefix reuse,
# tp=2 mesh, disagg handoff), host-sync audit still <= 1 sync per fused
# block with speculation on, int8 handoff round-trip bit-exactness +
# checkpoint round-trip, the repetitive-text acceptance-rate floor, and
# the program cache-key audit — plus the learned-proposer matrix
# (Medusa-style heads + co-resident draft model: pinned-equal across
# suspend/resume, drain/migration, disagg, the codec-v5 envelope, the
# arbiter's batch-class draft registrant, per-method telemetry, and the
# decode_block=1 rider error); then a CPU smoke of the spec bench stage
# (per-proposer natural-text acceptance)
spec-check:
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/test_spec.py \
		tests/test_spec_learned.py -q
	JAX_PLATFORMS=cpu BENCH_ONLY=SPEC BENCH_RUNS=1 BENCH_SPEC_TOKENS=16 \
		$(PYTHON) bench.py

# chunked-prefill + paged decode-kernel gate (docs/PERFORMANCE.md §7),
# CPU-safe: pinned-equal chunked-vs-monolithic matrix (greedy + seeded
# top-k, prefix reuse, int8, tp=2 mesh, disagg handoff of a chunk-prefilled
# slot), host-sync audit stays <= 1/block with chunking on, Pallas paged
# decode-attention kernel vs dense reference in interpret mode, and the
# program cache-key audit; then a CPU smoke of the chunked bench stage
# (decode ITL p99 under a batch-prefill flood, chunked on vs off)
chunk-check:
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/test_chunked.py -q
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/test_ops.py -q \
		-k PagedDecodeAttention
	JAX_PLATFORMS=cpu BENCH_ONLY=CHUNKED BENCH_RUNS=1 \
		BENCH_CHUNK_TOKENS=96 $(PYTHON) bench.py

# generation-forensics gate (docs/OBSERVABILITY.md), CPU-safe: timeline
# ledger unit + scheduler-integration tests, the stitched-trace two-engine
# disagg e2e (one trace id -> gateway + prefill + export/import + decode
# spans, /stats/timeline lifecycle for a chunked + speculative request),
# handoff codec v2 back-compat bit-exactness, QoS-through-frame, host-sync
# audit with the ledger on; then the obs_overhead bench smoke (decode ITL
# ledger on vs off + spans/s)
forensics-check:
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/test_forensics.py -q
	JAX_PLATFORMS=cpu BENCH_ONLY=OBS_OVERHEAD BENCH_RUNS=1 \
		BENCH_OBS_TOKENS=24 $(PYTHON) bench.py

# batched multi-LoRA gate (docs/MULTITENANT.md), CPU-safe: the
# null-adapter pinned-equal matrix (plain/top-k/spec/chunked/prefix
# reuse/int8/tp=2/disagg handoff), per-slot gather vs solo runs,
# adapter-salted prefix isolation, adapter-pool LRU + refcount pinning,
# HBM memory-manager ledger + enforcement, handoff codec v4 adapter
# rejection, program-key audit, host-sync audit, RandomABTest adapter
# traffic split; then the mixed-adapter-vs-swap bench smoke
lora-check:
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/test_lora.py -q
	JAX_PLATFORMS=cpu BENCH_ONLY=LORA BENCH_RUNS=1 \
		BENCH_LORA_TOKENS=16 $(PYTHON) bench.py

tiers-check:
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/test_tiers.py -q
	JAX_PLATFORMS=cpu BENCH_ONLY=tiered BENCH_SECONDS=2 BENCH_RUNS=1 \
		$(PYTHON) bench.py

# chip-packing gate (docs/PACKING.md), CPU-safe: arbiter grant ordering /
# preemption policy / hysteresis units, suspend-store byte accounting,
# the pinned-equal suspend/resume matrix (greedy, seeded top-k, int8 KV,
# adapter-salted, prefix reuse), the arbiter-driven E2E suspend of a real
# batch scheduler, and the host-ledger release-accounting regression;
# then a smoke of the bench packing stage (3 co-resident deployments:
# interactive p99 sole vs packed, batch goodput curve, zero mid-traffic
# compiles)
pack-check:
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/test_packing.py -q
	JAX_PLATFORMS=cpu BENCH_ONLY=PACKING BENCH_RUNS=1 \
		BENCH_PACK_TOKENS=16 $(PYTHON) bench.py

# chaos-plane gate (docs/RESILIENCE.md), CPU-safe: fault-plan grammar +
# selector determinism + disarmed inertness, retry-budget/circuit-breaker
# degradation, the live-migration bit-identity matrix (greedy, seeded
# top-k, int8 KV, LoRA-salted) with abort/no-peer/torn-frame fallbacks,
# the fake-apiserver control-plane e2e (retry ladder, token rotation,
# watch 410 storms); then the chaos bench smoke (recovery p50/p99,
# dropped/corrupted streams must be 0, disarmed gate cost)
chaos-check:
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/test_chaos.py \
		tests/test_kubesim.py -q
	JAX_PLATFORMS=cpu BENCH_ONLY=CHAOS BENCH_RUNS=1 \
		BENCH_CHAOS_ROUNDS=3 $(PYTHON) bench.py

# fleet telemetry plane (docs/OBSERVABILITY.md): cluster aggregation,
# history rings, SLO burn rates; the bench stage proves counter-exact
# merges and an ok->page->ok burn transition under open-loop overload
fleet-check:
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/test_fleet.py -q
	JAX_PLATFORMS=cpu BENCH_ONLY=FLEET BENCH_RUNS=1 $(PYTHON) bench.py

# elastic pool autoscaler (docs/AUTOSCALING.md): annotation grammar +
# admission, the policy state machine on synthetic time, drain-based
# shrink idempotency, the kubesim 1->N->1 e2e; the bench stage proves
# the closed loop rides a diurnal trace without flapping or shedding
scale-check:
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/test_autoscale.py -q
	JAX_PLATFORMS=cpu BENCH_ONLY=ELASTIC BENCH_RUNS=1 $(PYTHON) bench.py

# tenant cost-attribution plane (docs/OBSERVABILITY.md "Cost attribution"):
# usage-meter units, bounded adapter cardinality under 500 synthetic
# adapters, the 3-tenant packed conservation test (attributed device
# seconds == fused-block wall seconds +-1%, zero mid-traffic compiles,
# sync audit green), counter-exact fleet merges, exemplar-linked
# /prometheus; the bench stage proves metering-on ITL overhead is noise
meter-check:
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/test_metering.py -q
	JAX_PLATFORMS=cpu BENCH_ONLY=USAGE BENCH_RUNS=1 $(PYTHON) bench.py

# LLM-native graphs (docs/GRAPHS.md): cascade router decision matrix +
# pinned both-path e2e with stitched cascade.route spans, guardrail
# policy pipeline + determinism contract both ways, embeddings endpoint
# + pinned pooled vectors under tp=2, semantic cache tier bounds +
# paraphrase hits + both-tier spec-roll flush, confidence-signal
# host-sync parity
graph-check:
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/test_graphllm.py -q

# invariant-aware static analysis (docs/STATIC_ANALYSIS.md): host-sync,
# program-key, pairing, env-registry, async-discipline, test-hygiene,
# ring-growth.
# Stdlib-only (no jax), so the bare CI lint job runs it without installs;
# fails on any finding not in sctlint-baseline.json and on stale
# baseline entries
lint-check:
	$(PYTHON) -m seldon_core_tpu.tools.sctlint

clean:
	find . -name __pycache__ -type d -exec rm -rf {} +
