# Dev workflow. Tests and rehearsals run on the CPU, on an 8-device virtual
# mesh (tests/conftest.py). The chip is reached only through the builder's
# chip tool, one process per chip: `python chip_smoke.py` is the command.
TEST_ENV = env JAX_PLATFORMS=cpu

.PHONY: test test-fast chip-smoke bench soak soak-fleet soak-fleet-proc soak-disagg lint train-report dist-report

# tpu-lint: static trace-safety analysis (ANALYSIS.md). AST-only — no
# jax import, ~1 s; gates `make test`.
lint:
	$(TEST_ENV) python tools/tpu_lint.py paddle_tpu

test: lint
	$(TEST_ENV) python -m pytest tests/ -x -q
	# slow-marked TP + multi-decode serving identity variants
	# (pytest.ini's addopts deselect them; the explicit -m opts back
	# in — tier-1 stays lean, the full gate still proves int8/wq
	# identity under TP and int8/snapshot identity under decode_steps)
	$(TEST_ENV) python -m pytest tests/test_serving_tp.py \
		tests/test_serving_multi.py -m slow -q
	# slow-marked cross-process/compile-cache/http secondary variants
	# (ISSUE 14; tier-1 keeps the probe-gated lifecycle + the named
	# integrity paths, the full gate runs the rest)
	$(TEST_ENV) python -m pytest tests/test_fleet_proc.py \
		tests/test_compile_cache.py tests/test_fleet_http.py \
		-m slow -q
	# tier-1 870s budget (PR 14): the heavy convergence/zoo smoke and
	# the routing-criterion mini-soak moved behind the slow marker —
	# the full gate still runs every one of them here
	$(TEST_ENV) python -m pytest tests/test_dit.py \
		tests/test_vision_zoo.py tests/test_loop_grad.py \
		tests/test_fleet_router.py -m slow -q

test-fast: lint
	$(TEST_ENV) python -m pytest tests/ -x -q -m "not slow"

# Both need a TPU and exit non-zero without one; run them through the chip
# tool. chip_smoke.py is the check the driver runs.
chip-smoke:
	python chip_smoke.py

bench:
	python bench.py

# Randomized fault-injection soak of the serving engine (ISSUE 3 + 15
# + 17): the 200-request acceptance run (multi-LoRA clean+chaos passes
# via --lora, tiered-KV spill off/clean/chaos via --spill) + extra
# seeds. CPU-only, minutes-bounded; excluded from tier-1 via the
# `slow` marker (pytest.ini addopts).
soak:
	$(TEST_ENV) python tools/soak_serving.py --requests 200 --seed 0 --lora --spill
	# trace-report smoke (ISSUE 10): re-read the trace the soak's
	# traced pass exported (stdlib-only)
	$(TEST_ENV) python tools/trace_report.py profiler_log/soak_trace.json
	$(TEST_ENV) python -m pytest tests/test_soak_serving.py -m slow -q

# Training-observability smoke (ISSUE 11): run a tiny monitored CPU
# training loop (--demo: trace + mid-run retrace), export the
# TrainingMonitor document, and re-read it with the stdlib-only
# reporter — OBSERVABILITY.md's end-to-end example.
train-report:
	$(TEST_ENV) python tools/train_report.py --demo profiler_log/train_trace.json

# Distributed-observability smoke (ISSUE 12): run a tiny threaded ZB
# pipeline, export one chrome-trace per rank (with a live comm_report
# riding along), then merge them with the stdlib-only reporter — the
# cross-process layout exercised in-process.
dist-report:
	$(TEST_ENV) python tools/dist_report.py --demo profiler_log \
	  --out profiler_log/dist_merged.json

# Multi-replica fleet chaos soak (ISSUE 7): seeded kill + stall of
# replicas mid-stream; zero-loss / bit-identity / routing criteria.
# CPU-only, minutes-bounded; excluded from tier-1 like `make soak`.
soak-fleet:
	$(TEST_ENV) python tools/soak_fleet.py --requests 120 --seed 0
	# trace-report smoke over the MERGED (host spans + request rows)
	# chrome trace the traced chaos pass exported
	$(TEST_ENV) python tools/trace_report.py profiler_log/soak_fleet_trace.json
	$(TEST_ENV) python -m pytest tests/test_soak_fleet.py -m slow -q

# Cross-process fleet chaos soak (ISSUE 14): real worker processes over
# the TCPStore mailbox — seeded kill -9 mid-stream, a permanently wedged
# worker, a slow-heartbeat worker, wire drop/duplicate, the cold-vs-warm
# compile-cache bench (>= 5x) and a rolling restart. 3 seeds.
soak-fleet-proc:
	$(TEST_ENV) python tools/soak_fleet.py --procs --requests 30 --seed 0
	$(TEST_ENV) python -m pytest tests/test_soak_fleet_proc.py -m slow -q

# Disaggregated prefill/decode chaos soak (ISSUE 18): role-split fleet
# with mid-flight KV handoff — prefill kill -9 with the kv_page stream
# half shipped, decode death mid-adopt, relay stalls with capped-backoff
# re-pulls, role-starved co-location fallback, the decode-TPOT
# comparison against chunked-prefill co-location, and the int8-KV
# variant. 3 chaos seeds inside the ladder.
soak-disagg:
	$(TEST_ENV) python tools/soak_fleet.py --disagg --requests 64 --seed 0
	$(TEST_ENV) python -m pytest tests/test_soak_fleet_disagg.py -m slow -q

# Sanitizer builds of the native extension (parity: reference
# SANITIZER_TYPE configure option). Runs the native test suite against an
# ASan/TSan build of the C++ TCPStore + shm ring.
sanitize-address:
	g++ -O1 -g -fPIC -shared -std=c++17 -fsanitize=address \
	  -I/usr/local/include/python3.12 \
	  paddle_tpu/_native/src/paddle_tpu_native.cc \
	  -o /tmp/_paddle_tpu_native_asan.so -lpthread -lrt
	@echo "ASan build OK: /tmp/_paddle_tpu_native_asan.so"

sanitize-thread:
	g++ -O1 -g -fPIC -shared -std=c++17 -fsanitize=thread \
	  -I/usr/local/include/python3.12 \
	  paddle_tpu/_native/src/paddle_tpu_native.cc \
	  -o /tmp/_paddle_tpu_native_tsan.so -lpthread -lrt
	@echo "TSan build OK: /tmp/_paddle_tpu_native_tsan.so"
