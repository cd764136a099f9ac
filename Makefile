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
# soak-tpu   — the same batteries on the chip (tools/soak.py --tpu).
#              The real-chip run is the only place Mosaic bf16 behavior
#              is exercised — run it after any kernel change.
# multihost  — 2- and 4-process Gloo collectives (DCN shape)
# native     — build the C++ optimizer/ingestion core
# obs-report — aggregate the repo's query event log
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
#              stamped bounds). Point it at another log with
#              OBS_LOG=<path>

PY ?= python
SEEDS ?= 10
OBS_LOG ?= .matrel_events.jsonl

.PHONY: test lint soak soak-tpu multihost native obs-report chaos

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
	$(PY) tools/soak.py all --tpu --seeds $(SEEDS)

multihost:
	$(PY) -m pytest tests/test_multihost.py -q

native:
	$(MAKE) -C native

obs-report:
	$(PY) -m matrel_tpu history --summary --check --log $(OBS_LOG)
	$(PY) -m matrel_tpu history --drift --check --log $(OBS_LOG)
	$(PY) -m matrel_tpu history --coeffs --check --log $(OBS_LOG)
	$(PY) -m matrel_tpu trace --export chrome --log $(OBS_LOG) \
		--out $(OBS_LOG).chrome.json
	$(PY) -m matrel_tpu why --audit --sample 8 --check
