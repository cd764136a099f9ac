# matrel_tpu developer entry points.
#
# lint       — matlint (AST hazard rules, tools/matlint.py) + the
#              concurrency sanitizer's static half (lock-order /
#              hold-span analysis, tools/lockcheck.py; LK1xx rules,
#              docs/CONCURRENCY.md) + the static-verifier self-check
#              over the plan-snapshot corpus (tools/plan_verify.py).
#              Runs repo-wide; rc != 0 on any finding/diagnostic.
#              `test` depends on it, and tests/test_matlint.py +
#              tests/test_lockcheck.py re-run it in-process so the
#              tier-1 pytest path cannot silently skip it either.
# test       — full CPU suite on the simulated 8-device mesh
# soak       — oracle fuzz batteries on CPU (fast sanity)
# soak-tpu   — on-chip soak behind a probe and hard timeouts;
#              result appended to PROGRESS.jsonl (tools/soak_guard.py).
#              The real-chip run is the only place Mosaic bf16 behavior
#              is exercised — run it after any kernel change.
# multihost  — 2- and 4-process Gloo collectives (DCN shape)
# native     — build the C++ optimizer/ingestion core
# bench      — the headline metric (TPU; probe + measurement child)
# obs-report — aggregate the repo's query/bench/soak event log
#              (.matrel_events.jsonl — the history-server analogue);
#              --check on the summary exits nonzero on any UN-CLEARED
#              SLO alert (a log ending mid-incident must not read
#              green), then the round-9 smokes over the same log: the
#              cost-model drift audit (history --drift --check), the
#              closed-loop gate (history --coeffs --check — a firing
#              rank flag with no re-plan round fails the report) and a
#              chrome-trace export of the tracing spans, then the
#              tier-4 audit-replay gate (why --audit: sampled served
#              answers re-executed fresh and proved within their
#              stamped bounds). Point it at a dry-drill log with
#              OBS_LOG=/tmp/matrel_batch_dry/events.jsonl

PY ?= python
SEEDS ?= 10
OBS_LOG ?= .matrel_events.jsonl

.PHONY: test lint soak soak-tpu multihost native bench tpu-batch \
        tpu-batch-dry obs-report chaos

lint:
	$(PY) tools/matlint.py
	$(PY) tools/lockcheck.py
	$(PY) tools/plan_verify.py

test: lint
	$(PY) -m pytest tests/ -q

soak:
	$(PY) tools/soak.py all --seeds 25

# resilience acceptance: a mixed serve stream under a seeded fault
# schedule (every instrumented site) must converge-to-correct-or-
# typed-failure with zero hangs (tools/chaos_drill.py), then the
# randomized chaos soak battery on top (docs/RESILIENCE.md)
chaos:
	$(PY) tools/chaos_drill.py
	$(PY) tools/soak.py chaos --seeds 25

soak-tpu:
	$(PY) tools/soak_guard.py --seeds $(SEEDS)

multihost:
	$(PY) -m pytest tests/test_multihost.py -q

native:
	$(MAKE) -C native

bench:
	$(PY) bench.py

tpu-batch:
	sh tools/tpu_batch.sh

# fire-drill: the WHOLE staged capture batch on the CPU backend
# at toy sizes (VERDICT r5 Next #2) — proves every step runs and emits
# its parseable artifact, so chip time is spent measuring,
# not debugging the harness. tests/test_batch_dry.py asserts the
# artifacts.
tpu-batch-dry:
	sh tools/tpu_batch.sh --dry

obs-report:
	$(PY) -m matrel_tpu history --summary --check --log $(OBS_LOG)
	$(PY) -m matrel_tpu history --drift --check --log $(OBS_LOG)
	$(PY) -m matrel_tpu history --coeffs --check --log $(OBS_LOG)
	$(PY) -m matrel_tpu trace --export chrome --log $(OBS_LOG) \
		--out $(OBS_LOG).chrome.json
	$(PY) -m matrel_tpu why --audit --sample 8 --check
