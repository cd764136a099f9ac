#!/bin/sh
# One-command TPU capture batch. Every step probes first and runs under
# hard timeouts, one process on the chip at a time; each line a tool
# prints is stamped with the device it ran on. Results: stdout JSON
# lines per tool + structured entries in PROGRESS.jsonl (soak_guard,
# north_star_sweep).
#
# --dry (or MATREL_BATCH_DRY=1): the fire-drill (VERDICT r5 Next #2).
# Runs the SAME step sequence end-to-end on the CPU backend at toy
# sizes, with every artifact redirected under MATREL_BATCH_DRY_DIR
# (default /tmp/matrel_batch_dry) so a drill can never pollute the
# real capture history (PROGRESS.jsonl, cpu_baseline.json, the on-chip
# autotune table, the obs event log). `make tpu-batch-dry` runs it;
# tests/test_batch_dry.py asserts each step's parseable artifact — chip
# time is spent measuring, not debugging the harness.
set -u
cd "$(dirname "$0")/.."
log() { echo "$(date '+%H:%M:%S') $*"; }

DRY=0
[ "${1:-}" = "--dry" ] && DRY=1
[ "${MATREL_BATCH_DRY:-0}" = "1" ] && DRY=1
SEEDS=8
AUTOTUNE_TABLE=autotune_v5e_1chip.json
if [ "$DRY" = 1 ]; then
    DRY_DIR="${MATREL_BATCH_DRY_DIR:-/tmp/matrel_batch_dry}"
    mkdir -p "$DRY_DIR"
    export JAX_PLATFORMS=cpu
    export XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=8"
    # artifact redirects — nothing a drill writes lands in the repo
    export MATREL_PROGRESS_PATH="$DRY_DIR/progress.jsonl"
    export MATREL_SOAKLOG_PATH="$DRY_DIR/soaklog.jsonl"
    export MATREL_OBS_EVENT_LOG="$DRY_DIR/events.jsonl"
    export MATREL_OBS_FLIGHT_RECORDER_PATH="$DRY_DIR/flight.json"
    export MATREL_DRIFT_TABLE_PATH="$DRY_DIR/drift.json"
    export MATREL_BENCH_CPU_CACHE="$DRY_DIR/cpu_baseline.json"
    AUTOTUNE_TABLE="$DRY_DIR/autotune_dry.json"
    # toy sizes: same code paths, CPU-feasible scales
    export MATREL_DRY=1
    export MATREL_BENCH_N=512 MATREL_BENCH_REPEATS=3
    export MATREL_SPGEMM_N=8192 MATREL_SPGEMM_CMP_N=4096
    export MATREL_SPK_N=1024 MATREL_SPK_BS=64 MATREL_SPK_REPEATS=3 \
           MATREL_SPK_AUTOTUNE_SIDE=1024 \
           MATREL_SPK_TABLE="$DRY_DIR/spk_autotune.json"
    export MATREL_FUSION_N=256 MATREL_FUSION_K=64 \
           MATREL_FUSION_REPEATS=5 MATREL_FUSION_INNER=4
    export MATREL_SERVE_N=256 MATREL_SERVE_K=64 \
           MATREL_SERVE_QUERIES=18 MATREL_SERVE_MEAS=3
    export MATREL_CSE_N=512 MATREL_CSE_COLS=128 \
           MATREL_CSE_VARIANTS=8 MATREL_CSE_MEAS=3
    export MATREL_FLEET_N=192 MATREL_FLEET_QUERIES=7 \
           MATREL_FLEET_REPLAYS=2
    export MATREL_TRAFFIC_SLICES=2
    export MATREL_STREAM_N=256 MATREL_STREAM_EDGES=8 \
           MATREL_STREAM_UPDATES=3 MATREL_STREAM_K=16
    export MATREL_TRAFFIC_SECONDS=5 MATREL_TRAFFIC_TAIL_SECONDS=2.5 \
           MATREL_TRAFFIC_CAL=300 MATREL_TRAFFIC_N=48
    export MATREL_PRECISION_N=256 MATREL_PRECISION_REPEATS=3
    export MATREL_COEFFS_N=128 MATREL_COEFFS_K=64 \
           MATREL_COEFFS_MEAS=3 MATREL_COEFFS_INNER=4
    export MATREL_RESHARD_N=256 MATREL_RESHARD_REPEATS=3
    export MATREL_SPILL_N=128 MATREL_SPILL_MATS=4 \
           MATREL_SPILL_REPEATS=2
    export MATREL_NS_N=2048
    export MATREL_GRAM3_K=64 MATREL_GRAM3_PANEL=4096 MATREL_GRAM3_NPANELS=2
    export MATREL_GRAMFULL_N=200000 MATREL_GRAMFULL_K=64 \
           MATREL_GRAMFULL_PANEL=25000
    export MATREL_AUTOTUNE_SIDES=256 MATREL_AUTOTUNE_DTYPES=float32
    export MATREL_AUTOTUNE_SPMV=2000,20000
    export MATREL_RACE_SEEDS=2 MATREL_RACE_QUERIES=6
    SEEDS=2
    log "TPU batch DRY fire-drill (CPU backend; artifacts in $DRY_DIR)"
fi

log "TPU batch start"
log "--- bench.py (headline, BENCH row 1)"
python bench.py
log "--- soak_guard (on-chip oracle soak)"
python tools/soak_guard.py --seeds $SEEDS
log "--- bench.py --spgemm (S x S tile-intersection SpGEMM row, staged this round)"
python bench.py --spgemm
log "--- bench.py --sparse-kernels (structure-specialized kernel sweep + autotune replay, staged this round)"
python bench.py --sparse-kernels
log "--- bench.py --fusion (fused-vs-staged region sweep, staged this round)"
python bench.py --fusion
log "--- bench.py --serve (repeated-traffic serving QPS row, staged this round)"
python bench.py --serve
log "--- bench.py --cse (shared-interior CSE batch + plan-template row, staged this round)"
python bench.py --cse
log "--- bench.py --fleet (multi-slice fleet scale-out QPS + kill drill, staged this round)"
python bench.py --fleet
log "--- bench.py --stream (streaming IVM delta-patch vs recompute row, staged this round)"
python bench.py --stream
log "--- bench.py --precision (bf16/int precision-tier sweep + error bounds, staged this round)"
python bench.py --precision
log "--- bench.py --reshard (staged-vs-naive reshard sweep, staged this round)"
python bench.py --reshard
log "--- bench.py --coeffs (calibrated-vs-analytic planner row, staged this round)"
python bench.py --coeffs
log "--- bench.py --spill (spill-tier sweep + cold-vs-thawed restart row, staged this round)"
python bench.py --spill
log "--- bench_all.py (all BASELINE rows)"
python bench_all.py
log "--- topology_flip (ICI/DCN-weighted planner flip proof, staged this round)"
python tools/topology_flip.py
log "--- flight_drill (obs tier 2: flight recorder + chrome trace + drift smoke, staged this round)"
python tools/flight_drill.py
log "--- chaos_drill (resilience: seeded fault schedule over a mixed serve stream, staged this round)"
python tools/chaos_drill.py
log "--- provenance_drill (obs tier 4: answer lineage on every serve path + full audit replay, staged this round)"
python tools/provenance_drill.py
log "--- traffic (open-loop overload harness: weighted tenants, brownout, typed shed, staged this round)"
python tools/traffic.py
log "--- traffic --slo (SLO burn-rate alert fire/clear proof + live metrics endpoint, staged this round)"
python tools/traffic.py --slo
log "--- traffic --slices (open-loop fleet drill: placement spread, directory hits, mid-stream slice kill, staged this round)"
python tools/traffic.py --slices
log "--- race_drill (concurrency sanitizer: seeded serve/fleet interleavings under runtime lockdep, staged this round)"
python tools/race_drill.py
log "--- north_star_sweep (VERDICT #10 residual)"
python tools/north_star_sweep.py
log "--- gram_manual3 (symmetric-Gram microbench, BASELINE row 3 support)"
python tools/gram_manual3.py
log "--- gram_sym_full (10Mx1k linreg, symmetric 2-pass Gram, BASELINE row 3)"
python tools/gram_sym_full.py
log "--- autotune_capture (re-capture table under round-4 tie rules)"
python tools/autotune_capture.py "$AUTOTUNE_TABLE"
log "TPU batch done"
