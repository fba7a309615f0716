# Test slices for CI sharding and local iteration. Each slice targets
# roughly 10 minutes on a single core; the full suite (`make test`) is
# the union and takes ~45 minutes. Markers are registered in
# pyproject.toml — a typo'd marker is a collection error, not a silently
# empty slice.

PYTEST ?= python -m pytest
PYTEST_ARGS ?= -q

.PHONY: test test-kernel test-fast test-chaos test-byzantine test-storage \
	test-observability test-sync test-pipeline test-exec test-trie \
	test-mesh test-wan test-rs native lint sanitize \
	sanitize-tsan

# crypto/accelerator kernels: BLS12-381 group law + subgroup checks,
# TPKE, threshold signatures, JAX ops, kernel cache, native C++ backend.
# mesh-marked tests are excluded: their shard_map compiles belong to the
# dedicated mesh job ("make test-mesh") so a kernel-shard retry never
# re-pays them
test-kernel:
	$(PYTEST) $(PYTEST_ARGS) -m "kernel and not mesh"

# batched Reed-Solomon engine (ops/rs_batch.py + consensus/rbc_batcher.py):
# 200-seed scalar-vs-batch differentials, GF(2^16) codec, era-batcher
# dedupe/memo semantics, stale-.so fallback, on-vs-off block-hash identity
# on both engines. The slice to run after touching RBC or the RS codecs.
test-rs:
	$(PYTEST) $(PYTEST_ARGS) tests/test_rs_batch.py

# everything that is neither a kernel test nor a fault-injection run:
# consensus, storage, network, RPC, node lifecycle — the quick sanity
# slice to run after most changes
test-fast:
	$(PYTEST) $(PYTEST_ARGS) -m "not kernel and not chaos and not crash and not slow and not wan"

# fault injection + durability: seeded loss/partition chaos matrices,
# crash-point injection, SIGKILL-restart recovery ("not mesh": the
# slow-marked mesh differentials run in their own job, not here)
test-chaos:
	$(PYTEST) $(PYTEST_ARGS) -m "(chaos or crash or slow) and not mesh and not wan"

# smart-malicious adversaries: the strategy fleet (equivocate/withhold/
# relay/spam/vote-flip), dual-engine verdict identity, evidence
# durability + fsck, malicious-protocol subclass tests. The slice to run
# after touching consensus/adversary.py, consensus/evidence.py, the
# first-seen latches (era.py / consensus_rt.cpp opq_latch) or the
# evidence RPC/report surfaces
test-byzantine:
	$(PYTEST) $(PYTEST_ARGS) -m "byzantine and not slow"

# durable-store engines: LSM differential/crash/compaction tests, trie +
# state snapshots, crash-point matrix, fsck, CLI db verbs. Overlaps the
# other slices on purpose — it is the slice to run after storage changes
# (tests/native/sanitize.sh re-runs the non-slow part under ASan/UBSan)
test-storage:
	$(PYTEST) $(PYTEST_ARGS) -m storage

# flight recorder + metrics: span tracer, native trace rings + merge
# layer, era phase reports, Prometheus surface
test-observability:
	$(PYTEST) $(PYTEST_ARGS) -m observability

# consensus era pipelining: the windowed scheduler (on-vs-off block-hash
# identity, two-run bit-identity under seeded faults), journal GC across
# the overlap window, crash-replay of in-flight eras, stall reporting.
# The slice to run after touching the pipeline driver (native_rt.py
# pipeline_*/run_front/run_tail, devnet._run_eras_pipelined, era.py GC)
test-pipeline:
	$(PYTEST) $(PYTEST_ARGS) -m pipeline

# synchronization: the multi-peer fast-sync scheduler (failover, request
# ids, bounded frontier, bans, snapshot shipping) + the block
# synchronizer. The slice to run after touching core/fast_sync.py,
# core/synchronizer.py or the trie-serving wire kinds
test-sync:
	$(PYTEST) $(PYTEST_ARGS) -m "sync and not slow"

# block execution: the randomized deferred-vs-immediate freeze
# differential over executor blocks (receipts + roots + trie node sets
# bit-identical), the hashing routes over the benchmark's block shapes,
# delta checkpoints, canonical ordering, sharded pool admission. The
# slice to run after touching core/block_manager.py, core/execution.py,
# storage/state.py checkpoints or core/tx_pool.py
test-exec:
	$(PYTEST) $(PYTEST_ARGS) -m exec

# merkleization: the 200-seed deferred-vs-immediate apply_many
# differential (roots + node sets + pending buffers), the hashing
# threads' byte floor, the node cache across freezes. The slice to run
# after touching storage/trie.py apply_many/_bulk, the batch keccak, or
# the StateManager streamed commit
test-trie:
	$(PYTEST) $(PYTEST_ARGS) -m trie

# multi-device mesh crypto: the shard_mapped era pipeline on 8 forced
# virtual host devices (tests/test_mesh.py + test_warmup.py) — the
# mesh-vs-single-device differential, consensus-on-mesh end-to-end, mesh
# warmup through the persistent kernel cache. Includes the slow-marked
# differentials; the CI 'mesh' job runs exactly this slice so the
# skip-on-unsupported guard can never hide the suite everywhere
test-mesh:
	env JAX_PLATFORMS=cpu \
		XLA_FLAGS="--xla_force_host_platform_device_count=8" \
		$(PYTEST) $(PYTEST_ARGS) -m mesh

# WAN survival: link-shaper determinism + unit surface, RTT-adaptive
# recovery, the versioned-wire handshake/downgrade interop, and the
# rolling-upgrade drill (slow-marked legs included). The slice to run
# after touching network/faults.py LinkShaper, network/rtt.py,
# network/wire.py versioning, or core/fleet.py
test-wan:
	$(PYTEST) $(PYTEST_ARGS) -m wan

test:
	$(PYTEST) $(PYTEST_ARGS)

# the native consensus/crypto shared library (no-op when up to date;
# python loaders also rebuild on demand via source-mtime checks)
native:
	$(MAKE) -C lachain_tpu/crypto/native
	$(MAKE) -C lachain_tpu/consensus/native

# static analysis: the repo-invariant linter (determinism hazards in
# consensus modules, lock-acquisition-order cycles, persist-before-
# transmit) always runs; ruff runs when installed (config lives in
# pyproject.toml so CI and local runs agree — the container image does
# not ship ruff, so its absence is a skip, not a failure)
lint:
	python tools/check_invariants.py
	@if command -v ruff >/dev/null 2>&1; then \
		ruff check .; \
	else \
		echo "ruff not installed -- skipping style pass (config in pyproject.toml)"; \
	fi

# ASan/UBSan over the native engines: C++ harness legs + fuzzers, then
# the Python test suites against sanitized builds of all three shared
# libraries (loader override envs). FUZZ_SECONDS trims the fuzz legs.
sanitize:
	cd tests/native && ./sanitize.sh

# ThreadSanitizer over the native engines: rebuilds libllsm/libconsensus_rt/
# libbls381 with -fsanitize=thread and drives them through the real
# multi-threaded Python test slices (storage/trie/exec/pipeline). Any
# unsuppressed report fails the target (TSAN_OPTIONS exitcode + log scan).
sanitize-tsan:
	cd tests/native && ./tsan.sh
