# imaginary-tpu build/test targets (role of the reference's Makefile)

.PHONY: all native native-entropy dct-parity test bench bench-cache bench-obs bench-deadline bench-qos bench-memory bench-device bench-stages chaos serve clean gate lint check

all: native test

# No-red-snapshot gate (VERDICT r2 next #1): run before ANY commit meant
# to be a round snapshot. Green means: lint is clean, full suite passes,
# the driver's entry + 8-device dryrun execute, bench.py emits its JSON
# line, and the chaos drill holds its invariants (an explicit CPU run,
# BENCH_PLATFORM=cpu — the gate checks the machinery, not the chip).
gate: lint native-entropy dct-parity test chaos
	python __graft_entry__.py
	BENCH_DURATION=2 BENCH_THREADS=8 BENCH_PLATFORM=cpu python bench.py || \
	  { echo "bench.py failed - snapshot NOT green"; exit 1; }
	BENCH_DURATION=2 BENCH_CONCURRENCY=8 python bench_cache.py || \
	  { echo "bench_cache.py failed - snapshot NOT green"; exit 1; }
	BENCH_DURATION=2 BENCH_CONCURRENCY=8 python bench_obs.py || \
	  { echo "bench_obs.py failed - snapshot NOT green"; exit 1; }
	BENCH_DURATION=2 BENCH_CONCURRENCY=8 python bench_deadline.py || \
	  { echo "bench_deadline.py failed - snapshot NOT green"; exit 1; }
	BENCH_DURATION=2 BENCH_CONCURRENCY=8 python bench_qos.py || \
	  { echo "bench_qos.py failed - snapshot NOT green"; exit 1; }
	BENCH_DURATION=4 BENCH_CONCURRENCY=6 python bench_memory.py || \
	  { echo "bench_memory.py failed - snapshot NOT green"; exit 1; }
	BENCH_PLATFORM=cpu python bench_stages.py || \
	  { echo "bench_stages.py byte-touch/spill gates failed - snapshot NOT green"; exit 1; }
	BENCH_DURATION=4 BENCH_THREADS=8 BENCH_COHERENCE_ONLY=1 python bench_workers.py || \
	  { echo "bench_workers.py fleet-coherence gates failed - snapshot NOT green"; exit 1; }
	BENCH_DURATION=4 BENCH_THREADS=8 BENCH_MULTIHOST_ONLY=1 python bench_workers.py || \
	  { echo "bench_workers.py multi-host gates failed - snapshot NOT green"; exit 1; }
	@echo "GATE GREEN: itpucheck + tests + dryrun + chaos + bench + cache/obs/deadline/qos/memory/stages/coherence/multihost benches all pass"

# Chaos drill (ISSUE 4 + ISSUE 6 + ISSUE 7 + ISSUE 10 + ISSUE 11): the
# deadline/failpoint/devhealth/pressure/integrity/fleet suites, then
# nine soaks — a
# flaky-origin row (source.fetch=error(0.2): availability >= 95%, honest
# 502/503/504 mapping, deadline boundedness, ledgers at rest), a
# chip-loss row (device.chip_error on the primary device mid-run:
# failover keeps serving, the sick chip quarantines alone, the probe
# re-admits it after its cooldown), a hedge A-B row, an OOM-storm row
# (device.oom at p=0.5: every request completes via bisect-retry or host
# routing, the breaker never opens, ledgers at rest), an SDC-storm row
# (device.corrupt[0] under --integrity sample 1.0: zero corrupted bytes
# served, every mismatch re-served from the verified copy, the lying
# chip quarantined alone, availability >= 99%), and a fail-slow row
# (device.slow[0]=delay(250ms): the limping chip demotes on the golden-
# probe latency comparison and fleet p99 recovers to within 1.5x of the
# healthy baseline). The two forced CPU devices make the multi-chip
# fault-domain path run on hardware-less CI; real multi-chip hosts
# exercise it natively. Rows 7-9 (ISSUE 11) then boot REAL 2-worker
# SO_REUSEPORT fleets with the shared cache armed and kill processes:
# SIGKILL mid-write storm (>=99% availability, zero corrupt-byte
# serves, the torn slot reclaimed), SIGSTOP-past-liveness zombie (the
# revived worker is epoch-fenced: reads ok, publishes refused), and a
# SIGHUP rolling restart under open-loop load (100% availability,
# per-index epochs monotonic); counters archived to
# artifacts/chaos_fleet.json. Rows 11-12 (ISSUE 19) arm --fleet-coherence
# on the same fleet shape: SIGKILL the digest owner mid-coalesce (>=99%
# availability, fleet singleflight bound on publishes, claim table at
# rest after one sweep) and a SIGSTOP zombie owner (its identity refused
# at claim_acquire, a deposed live holder read STALE and swept); counters
# archived to artifacts/chaos_ownership.json. Row 13 (ISSUE 20) boots a
# REAL 2-host cluster (two cross-peered supervisors, --router) and
# SIGKILLs one whole host mid-storm: availability holds >= 99% on the
# survivor, its fleet metrics stay monotonic, and the dead host rejoins
# under a bumped host epoch; counters archived to
# artifacts/chaos_multihost.json.
chaos:
	python -m pytest tests/test_failpoints.py tests/test_deadline.py tests/test_qos.py tests/test_devhealth.py tests/test_pressure.py tests/test_integrity.py tests/test_fleet.py tests/test_ownership.py -q -m 'not slow'
	BENCH_DURATION=4 BENCH_CONCURRENCY=8 \
	  XLA_FLAGS="--xla_force_host_platform_device_count=2" \
	  JAX_PLATFORMS=cpu python bench_chaos.py || \
	  { echo "chaos soak failed - resilience invariants violated"; exit 1; }

# Project-invariant static analyzer (imaginary_tpu/tools/itpucheck.py):
# stdlib-ast only, ships inside the package, so it ALWAYS runs — there
# is deliberately no "unavailable - SKIPPED" branch here. Exits nonzero
# on any unsuppressed finding; --json archives the finding count under
# artifacts/ next to the bench rows. See README "Static analysis".
check:
	python -m imaginary_tpu.tools.itpucheck --json artifacts/itpucheck.json

# correctness-class lint: itpucheck (always), then ruff (ruff.toml —
# syntax errors, undefined names, unused imports/variables/redefinitions).
# Ruff FAILS the gate when present; hosts without it skip with a notice
# (the bench containers don't ship it — CI images should).
lint: check
	@if python -m ruff --version >/dev/null 2>&1; then \
	  python -m ruff check .; \
	elif command -v ruff >/dev/null 2>&1; then \
	  ruff check .; \
	else \
	  echo "lint: ruff unavailable on this host - SKIPPED (pip install ruff to enable)"; \
	fi

native:
	python -m imaginary_tpu.native.build

# Entropy-codec kernel (codecs/jpeg_dct.py's native arm). Best-effort:
# hosts without a C++ toolchain serve on the numpy/python arms, so a
# failed build must not red the gate — the parity suite still runs.
native-entropy:
	python -m imaginary_tpu.native.build entropy || \
	  echo "native-entropy: toolchain unavailable - numpy/python arms serve"

# Decoder/encoder parity suite: every entropy arm (native when built,
# numpy, python) must produce byte-identical coefficients over the
# corpus, and the egress encoder must roundtrip exactly. Runs whether
# or not the native kernel built — the pure arms are the oracle.
dct-parity:
	python -m pytest tests/test_dct_codec.py tests/test_dct.py -q -m 'not slow'

test:
	python -m pytest tests/ -x -q

bench:
	python bench.py

bench-latency:
	python bench_latency.py

# cache-tier rows (zipf hot-URL + 32-way coalescing); exits nonzero when
# the zipf row shows zero hits or coalescing executed one run per request
bench-cache:
	python bench_cache.py

# headline throughput with tracing on vs off (cache-off zipf row), plus
# the cost-plane rows (--cost-attribution ABBA overhead; hog-flood /topz
# ranking with live-vs-offline bound_by agreement) and the 2-worker
# fleet tail-sampling row; exits nonzero on gross overhead, missing
# tracing response surfaces, or any cost/fleet gate breach
bench-obs:
	python bench_obs.py

# headline throughput with request deadlines on (generous budget) vs off;
# exits nonzero on gross overhead or any spurious shed/expiry
bench-deadline:
	python bench_deadline.py

# mixed-tenant overload isolation row (hog batch flood vs interactive
# tenant p99, qos on/off + unloaded anchor); exits nonzero when qos fails
# to improve the interactive p99 or breaches the isolation bound
bench-qos:
	python bench_qos.py

# raw-vs-dct transport A/B under a simulated slow link
# (BENCH_LINK_FIXED_MS / BENCH_LINK_MB_PER_S pace the staged bytes read
# off the wire ledger); exits nonzero when the dct arm's wire bytes are
# not >=4x below raw on the 1080p->thumbnail ladder or when either arm
# pays a post-prewarm compile. Rows archive to
# artifacts/transport_ab_<backend>.jsonl.
bench-device:
	BENCH_TRANSPORT_AB=1 BENCH_PLATFORM=cpu python bench_device.py
	BENCH_MESH_AB=1 BENCH_PLATFORM=cpu \
	  XLA_FLAGS="--xla_force_host_platform_device_count=4" \
	  python bench_device.py

# bomb + oversize-enlarge firehose, governor on vs off: the governed arm
# must hold >=95% well-formed availability (only 200/413/503/504) with
# peak RSS under the configured ceiling; the ungoverned arm must exceed
# that ceiling (BENCH_RSS_CEILING_MB tunes it); governed/ungoverned RSS
# peaks archive to artifacts/memory_firehose.json with a delta vs the
# previous run (regressions past +16 MB fail)
bench-memory:
	python bench_memory.py

# per-stage host-ceiling decomposition + the byte-touch ledger rows:
# end-to-end ns/byte and copies-per-request through the real app, the
# cache-hit audit gated on copies-per-hit == 1 on BOTH tiers (local LRU
# and fleet shm), and the spill-path dct shrink-on-load row gated >=2x
# over full-scale reconstruction. Archives artifacts/host_ceiling_*.json
# and artifacts/host_bytes_*.json.
bench-stages:
	BENCH_PLATFORM=cpu python bench_stages.py

docker:
	docker build -t imaginary-tpu .

serve:
	python -m imaginary_tpu --port 9000 --enable-url-source

clean:
	rm -f imaginary_tpu/native/_imaginary_codecs*.so
	rm -f imaginary_tpu/native/_imaginary_resample*.so
	rm -f imaginary_tpu/native/_imaginary_entropy*.so
	find . -name __pycache__ -type d -exec rm -rf {} +
