"""Typed configuration for matrel_tpu.

The reference (purduedb/MatRel) configures itself through SparkConf key-value
pairs (``spark.matfast.*`` keys — block size, broadcast threshold; see
SURVEY.md §5 "Config / flag system"). The TPU-native equivalent is a small
frozen dataclass threaded through the session, overridable from environment
variables (``MATREL_*``) or a plain dict.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any, Mapping, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class MatrelConfig:
    """Global knobs for planning and execution.

    Attributes:
      block_size: logical tile edge used for block-level reasoning (sparsity
        masks, cost model granularity). The reference defaults to 1000x1000
        MLlib blocks; on TPU we default to 512, a multiple of the 128-lane
        MXU tiling.
      mesh_shape: (rows, cols) of the 2D device mesh. ``None`` → derive a
        near-square mesh from ``jax.device_count()``.
      mesh_axis_names: names of the two mesh axes.
      broadcast_threshold_bytes: operands smaller than this are planned as
        Broadcast-MM (replicated sharding) — the analogue of MatRel's
        broadcast-variable threshold.
      strategy_override: force one of {"bmm", "cpmm", "rmm", "auto"} for
        every matmul, bypassing the cost model. "auto" = cost-based.
      sparsity_threshold: density below which a matrix is considered sparse
        by the planner/cost model.
      spgemm_density_threshold: S×S matmuls whose ESTIMATED output block
        density (ir/stats.matmul_density at tile granularity) is below
        this dispatch the tile-intersection SpGEMM kernel
        (ops/spgemm.py) — neither operand is densified. At or above it
        the multiply falls back to the densify path (SpMM over a
        densified right operand), where the MXU's dense throughput wins.
        0 disables SpGEMM entirely.
      spgemm_kernel_override: force one REGISTERED SpGEMM kernel id
        (ops/kernel_registry.py — "xla_gather", "pallas_generic",
        "pallas_band", "pallas_cluster", "pallas_powerlaw") for every
        dispatching S×S multiply, bypassing the registry's structure
        classification, the autotune table and the cost model. The
        soak battery's forcing knob and the degradation ladder's
        rung-3 escape hatch (resilience/degrade.py forces
        "xla_gather" there so a miscompiling specialized Pallas
        kernel cannot survive the retry ladder). An inadmissible
        override (a Pallas id with Pallas unavailable) falls back to
        the legacy default; an UNKNOWN id raises at selection. ""
        (the default) disables forcing.
      comm_alpha_bytes: per-collective-STEP latency charge for the
        planner's comm model, in per-device byte-equivalents (the α of
        an α-β model; ~1 µs of v5e ICI ≈ 200 kB). Stepped strategies
        pay it per step — SUMMA's ring 2·(g−1) times, cpmm's
        reduce-scatter once, each nonzero reshard once — so small
        latency-bound multiplies (BASELINE row 2 class) stop ranking
        purely by bytes. 0 restores the β-only model. The chain DP's
        comm proxy stays β-only (its native mirror is
        equivalence-fuzzed against the alpha-free closed forms).
      default_dtype: dtype for constructors that don't specify one.
      matmul_precision: jax.lax precision for dot_general ("default",
        "high", "highest"). bfloat16 inputs + "highest" ≈ f32 accumulate.
      keep_input_dtype: cast matmul results back to the common input dtype
        (f32 accumulation on the MXU, bf16 storage in HBM — halves the
        write bandwidth of bf16 pipelines; XLA fuses the cast into the
        matmul epilogue).
      use_pallas: enable hand-written Pallas kernels where available.
      pallas_interpret: ALSO run the Pallas paths on non-TPU backends in
        interpret mode. Testing/debug only — interpret is slow and
        elides bf16 rounding on casts; never a fast path.
      chain_opt: enable the matrix-chain DP reorder.
      join_pair_cap_entries: refuse to MATERIALISE a join result larger
        than this many entries (the pair matrix of join_on_value; the
        merged output of join_on_rows / join_on_cols). Only aggregated
        VALUE-joins stream and are exempt — index joins always
        materialise their output and hit the cap even under an
        aggregate.
      join_bruteforce_max_pairs: cap on na*nb for aggregated value-joins
        with BLACK-BOX (callable) merge/predicate, which must enumerate
        pairs chunkwise. Structured predicates ("eq","lt",...) use the
        O(n log n) sort path and are exempt.
      join_chunk_entries: per-chunk entry budget for the black-box
        streaming enumeration (bounds the live tile).
      plan_cache_max_plans / plan_cache_max_bytes: LRU bounds on the
        session's compiled-plan cache. Each cached plan pins its
        hoisted sparse payloads (extra_args) in device memory; the
        byte budget counts those, the plan bound the rest. Least-
        recently-used plans evict first.
      rewrite_rules: enable the algebraic rewrite pass.
      donate_intermediates: donate chain intermediates to XLA where legal.
      autotune: let MEASURED strategy timings override the cost model's
        matmul pick (SURVEY.md §7 hard part: "detecting when XLA's
        choice beats the explicit paths"). On first sight of a shape
        class the admissible strategies are timed on-device once; the
        winner is cached in-process AND persisted to autotune_table_path
        so the measurement survives the session.
      autotune_table_path: JSON file for the persisted measurement
        table. Empty → ".matrel_autotune.json" in the working directory.
      autotune_max_dim: shapes with max(n,k,m) above this are never
        measured inline (measuring allocates two square operands of
        that size); the cost model keeps those.
      obs_level: query-lifecycle observability (matrel_tpu/obs/).
        "off" (default — the bench config: zero event emission, zero
        extra device syncs on the query path), "on" (one JSONL event
        record per session query run + metrics registry updates; event
        assembly happens outside jitted code), "analyze" (additionally
        per-op wall-clock on every explain — equivalent to passing
        ``analyze=True`` to ``session.explain``).
      obs_event_log: JSONL event-log path (the Spark event-log
        analogue). Empty → ".matrel_events.jsonl" in the working
        directory. Read it back with ``python -m matrel_tpu history``.
      obs_metrics_port: in-process live metrics endpoint
        (matrel_tpu/obs/export.py; docs/OBSERVABILITY.md tier 3) — a
        stdlib-only background HTTP server on 127.0.0.1 serving
        ``/metrics`` (Prometheus text format) and ``/json`` (a JSON
        snapshot of the metrics registry's sketches, SLO states,
        brownout rung, breaker states, result-cache/IVM counters and
        drift flags — what ``python -m matrel_tpu top`` polls). 0
        (the default) starts NOTHING: zero exporter threads, zero
        endpoint objects (test-enforced, the flight-recorder
        structural-off precedent).
      slo_targets: declarative per-tenant service-level objectives
        (matrel_tpu/obs/slo.py; docs/OBSERVABILITY.md tier 3) —
        ``"gold:p95_ms=50,avail=0.999;bronze:avail=0.99"``. Each
        objective is tracked with multi-window burn-rate alerting
        (Google-SRE style: the fast window catches an incident while
        it burns, the slow window confirms it is sustained; see
        slo_fast_window_s / slo_slow_window_s / slo_burn_threshold);
        alert TRANSITIONS emit an ``alert`` event and land in the
        flight-recorder ring regardless of ``obs_level``. Latency
        objectives (``p50_ms``/``p90_ms``/``p95_ms``/``p99_ms``)
        count a served query against its budget when it resolves
        slower than the target; ``avail`` counts sheds, deadline
        misses and terminal errors. The pseudo-tenant ``ivm`` is fed
        by ``register_delta`` patch latency. "" (the default)
        constructs NO monitor objects and the query path is
        bit-identical (test-enforced). Validated at construction.
      slo_fast_window_s / slo_slow_window_s: the two burn-rate
        windows (seconds; fast < slow, validated). Defaults 60 s /
        1800 s — the 1 m / 30 m pairing; the traffic harness shrinks
        them to fit its phases.
      slo_burn_threshold: burn-rate multiple (error-budget
        consumption rate vs the sustainable rate 1.0) at which an
        objective FIRES — both windows must exceed it. Default 14.4
        (the Google SRE fast-page number: 2% of a 30-day budget in
        an hour).
      slo_burn_exit: the alert CLEARS when the fast window's burn
        falls below this (< slo_burn_threshold, validated — the
        separation is the hysteresis, the brownout-threshold
        discipline). Default 1.0: clear only once the budget stops
        shrinking.
      obs_flight_recorder: capacity of the in-memory flight-recorder
        ring (obs/trace.py) — the last N span/event records, kept
        INDEPENDENTLY of ``obs_level`` (an always-cheap deque append;
        no I/O, no event assembly) and dumped to a JSON artifact on
        VerificationError / compile failure / serve-batch failure or
        an explicit ``session.dump_flight_recorder()``, so a field
        failure leaves a post-mortem trail instead of one error
        string. 0 (the default) disables the recorder entirely — with
        ``obs_level="off"`` the query path then creates no span
        objects at all (the bench contract, test-enforced).
      obs_flight_recorder_path: dump-artifact path for the flight
        recorder. Empty → ".matrel_flight.json" in the working
        directory.
      drift_table_path: JSON file for the cost-model drift auditor's
        persisted calibration table (obs/drift.py — per-(strategy,
        shape-class, backend) measured-vs-estimated ratios,
        maintained by ``history --drift``). Empty →
        ".matrel_drift.json" next to the autotune table's default.
      verify_plans: static plan verification (matrel_tpu/analysis/ —
        the pre-execution invariant checker). "off" (default: zero
        verifier work on the compile path), "warn" (run every pass
        after planning, log diagnostics, never fail the query), or
        "error" (raise analysis.VerificationError on any error-severity
        diagnostic BEFORE anything traces or runs on hardware — the
        array-redistribution-checker discipline of arXiv:2112.01075).
        ``session.verify(expr)`` and ``explain()`` run the passes
        regardless of this gate; it only controls the compile path.
      hbm_budget_bytes: per-device HBM budget that a PLAN's reckoned
        peak is held to (planner.plan_hbm_bytes): the catalog tables
        the plan reads (residents), every intermediate alive at a
        product, the product's output at its storage dtype and the
        strategy's transient — not one product's own working set
        (PR 27; VERDICT r5 Weak #3/Next #6 for the gate itself). The
        gate takes the SMALLER of this and what the mesh's devices
        report (``memory_stats()["bytes_limit"]``,
        core.mesh.hbm_limit_bytes), so a budget above the chip cannot
        admit a plan the chip cannot allocate. The default is 15.5 GiB:
        a v5e reports 16,909,334,528 B (15.75 GiB, 270 MB under the
        16 GiB this field used to default to) and its compiler keeps
        258 MB of that back from a program (chip readings, PR 22 and
        PR 27). The panelled rmm derives its panel counts from the
        same budget (strategies.rmm_panels); there is no other knob.
        0 disables the gate (divisibility-only admissibility, the
        pre-round-6 behaviour). The xla fallback is estimated and
        gated like the others; where nothing fits, the candidate that
        needs least is handed over and the plan says so
        (``refused_hbm``).
      result_cache_max_bytes: byte budget for the session's cross-query
        MATERIALIZED-RESULT cache (matrel_tpu/serve/result_cache.py —
        the MatFast persist/RDD-cache analogue): executed query results
        are kept on device keyed by the CANONICAL STRUCTURAL plan key
        (session._plan_key — never id()-keyed, the ML005 hazard class),
        so a repeated query answers without compiling or executing and
        a query CONTAINING a previously-executed subplan enters
        planning with that subtree replaced by an already-laid-out
        leaf (infer_layout/comm_cost credit the reuse). LRU eviction
        past the budget; a catalog rebind invalidates every dependent
        entry. 0 (the default) disables the cache entirely and is
        bit-identical to the uncached behaviour — plans, results and
        the plan-snapshot corpus unchanged.
      result_cache_max_entries: entry-count bound on the result cache
        (LRU, like plan_cache_max_plans). The byte budget counts each
        entry's RESULT array, but an entry's pins also keep the
        query's INPUT matrices alive (the plan cache's pinning
        contract) — tiny results over huge ad-hoc inputs could
        otherwise retain unbounded device memory while staying "within
        budget". The count bound caps that retention.
      serve_max_batch: micro-batched admission width — the most queries
        ``session.submit``'s admission loop coalesces into one
        MultiPlan (one fusion/CSE domain, shared leaf transfers).
        ``session.run_many`` batches whatever it is handed; this knob
        bounds only the async pipeline's coalescing.
      serve_max_inflight: bound on dispatched-but-unsynced batches the
        async pipeline keeps in flight. JAX's async dispatch lets the
        host optimize/verify/trace query N+1 while the device executes
        query N; past this depth the admission loop blocks on the
        oldest batch so host planning never runs unboundedly ahead of
        the device.
      precision_sla: the session-default per-query accuracy SLA for
        precision-tiered matmul execution (parallel/planner.py tier
        chooser; docs/PRECISION.md). "default" (the default) disables
        tiering entirely — no tier is ever stamped and every lowering
        is bit-identical to the pre-tier engine (plan snapshots
        unchanged). The named SLAs: "exact" (no accuracy loss vs
        today's f32/HIGHEST path; integer-shaped workloads route to
        the exact int32 MXU path), "high" (~f32 accuracy allowed —
        the bf16 k-pass split-summation tier, arXiv:2112.09017),
        "fast" (single-pass bf16 MXU rate; documented bf16 error
        bound). An explicit dtype ("float32", "bfloat16", "bf16x3",
        "int32", "int8") pins the tier directly. Per-query override:
        ``session.run(expr, precision=...)`` (also run_many/submit,
        and SQL's ``... PRECISION 'fast'`` clause).
      precision_enable_bf16: allow the bf16 tiers (bf16x1/bf16x3) in
        the SLA chooser. Off → "high"/"fast" degrade to f32. Explicit
        dtype SLAs bypass the gate (an explicit ask is an ask).
      precision_enable_int: same gate for the integer-exact tiers
        (int32/int8).
      fault_inject: fault-injection spec for the resilience layer
        (matrel_tpu/resilience/faults.py; docs/RESILIENCE.md) —
        semicolon-separated ``site:kind[:p=F|:n=K][:max=M]`` rules
        raising typed ``InjectedFault`` at the engine's instrumented
        choke points (compile, lower, strategy, execute, rc_probe,
        serve_admit, checkpoint) on a DETERMINISTIC seeded schedule.
        "" (the default) injects nothing and constructs nothing
        (test-enforced). Validated at construction.
      fault_inject_seed: seed of the injection schedule's per-rule
        random streams (and the retry policy's backoff jitter) — same
        spec + same seed = bit-identical fault schedule.
      retry_max_attempts: how many RETRIES a failed query gets past
        its first attempt (resilience/retry.py). Only failures the
        typed taxonomy classifies transient (RESOURCE_EXHAUSTED-class
        runtime errors, injected transients) retry — VerificationError
        and compile/shape errors never do. Each retry climbs one rung
        of the plan-degradation ladder (resilience/degrade.py). 0
        (the default) retries nothing.
      retry_backoff_ms / retry_backoff_mult / retry_jitter:
        exponential-backoff schedule between attempts — base delay,
        per-attempt multiplier, and symmetric jitter fraction (seeded
        by fault_inject_seed, so schedules are reproducible).
      deadline_ms: session-default per-query deadline. A query that
        has not produced a result when it expires raises the typed
        ``DeadlineExceeded`` — checked at admission and BETWEEN retry
        attempts (a running XLA dispatch is never interrupted). 0 (the
        default) = no deadline; per-call override via
        ``session.run(expr, deadline_ms=...)`` (also run_many/submit).
      serve_queue_max: bound on the async pipeline's admission queue.
        A ``submit`` against a full queue raises the typed
        ``AdmissionShed`` instead of growing the queue without bound —
        load shedding that protects the queries already admitted. 0
        (the default) keeps the historical unbounded queue. Expired-
        deadline entries are PURGED (resolved typed) at the shed
        decision point before the bound is enforced, so a queue full
        of dead entries never sheds live traffic (docs/OVERLOAD.md).
      serve_tenant_weights: per-tenant weighted-fair-queuing weights
        for the admission worker (serve/admission.py;
        docs/OVERLOAD.md) — ``"gold:4,silver:2,bronze:1"``. With
        weights set, each tenant gets its own admission queue and the
        worker pops entries in stride-scheduled proportion to weight
        (the YARN/Spark fair-scheduler analogue of PAPER.md [P1]'s
        multi-tenant operating point), so one chatty tenant cannot
        monopolize a MultiPlan or starve the stream. "" (the default)
        keeps ONE implicit tenant and is bit-identical to the
        historical FIFO admission order. Tenants not named here get
        weight 1.0. Validated at construction.
      serve_tenant_queue_max: per-tenant admission-queue bound. A
        tenant at its cap sheds typed ``AdmissionShed(tenant=...)``
        BEFORE the global ``serve_queue_max`` bound is consulted —
        per-tenant quota protects every OTHER tenant's share of the
        queue. 0 (the default) = no per-tenant cap.
      brownout_enable: the adaptive brownout controller
        (resilience/brownout.py; docs/OVERLOAD.md). Off (the default)
        constructs NO controller object and the serve plane is
        bit-identical. On: the admission worker samples queue depth,
        queue-wait p95 and deadline-miss rate over a sliding window
        and climbs a cumulative rung ladder under sustained pressure —
        rung 1 downshifts default-SLA queries to the "fast" precision
        tier (results stay SLA-key-isolated), rung 2 serves
        result-cache entries a rebind marked STALE to queries that
        declare a ``staleness_ms`` tolerance, rung 3 sheds
        lowest-weight tenants (typed) — descending with hysteresis
        when every signal falls below the (separated) exit thresholds.
      brownout_window: sliding-window length (admission-cycle samples)
        the controller's statistics cover.
      brownout_dwell: minimum samples between rung moves — the
        hysteresis dwell that stops the ladder oscillating on one
        noisy sample.
      brownout_wait_high_ms / brownout_wait_low_ms: queue-wait p95
        enter/exit thresholds. Enter pressure when p95 exceeds high;
        the wait signal reads calm only below low (low < high,
        validated — the separation IS the hysteresis).
      brownout_depth_high / brownout_depth_low: queue-depth enter/exit
        thresholds (same contract).
      brownout_miss_high / brownout_miss_low: deadline-miss-rate
        enter/exit thresholds over the window (fractions in [0, 1],
        low < high).
      breaker_threshold: per-plan-class circuit breakers
        (resilience/breaker.py; docs/OVERLOAD.md). 0 (the default)
        constructs NO breaker objects. > 0: consecutive TERMINAL
        failures of one plan class (the drift auditor's
        kind + pow2-shape-class key) — failures that already exhausted
        the retry budget — open that class's breaker, and further
        queries of the class fail FAST with the typed ``CircuitOpen``
        (carrying the half-open probe schedule) instead of burning
        compile/retry budget the healthy classes need. After
        ``breaker_cooldown_ms`` the breaker goes half-open and admits
        ``breaker_half_open_probes`` probe queries: a probe success
        closes it, a probe failure re-opens it for another cooldown.
      breaker_cooldown_ms: open→half-open cooldown (must be > 0).
      breaker_half_open_probes: concurrent probe budget in half-open
        (>= 1).
      reshard_peak_budget_bytes: peak per-device bytes a layout change
        (reshard) may have live during any one step of its lowering
        (matrel_tpu/parallel/reshard.py; docs/RESHARD.md — the
        arXiv:2112.01075 bounded-redistribution discipline). 0 (the
        default) keeps the legacy single-constraint path bit-
        identically — XLA emits whatever one-shot collective it likes,
        no ReshardPlan object is ever constructed (test-enforced).
        > 0: cross-axis layout changes lower as a verified step
        sequence (per-axis all_to_all / staged gathers) whose peak
        footprint fits the budget, the planner prices reshards from
        the plan's real per-axis bytes, and MV109 proves every stamped
        reshard's peak fits — the knob that lets near-HBM-limit
        operands move at all instead of being refused by MV105.
      fusion_enable: whole-plan program fusion (matrel_tpu/ir/fusion.py;
        docs/FUSION.md). Off (the default) is bit-identical to the
        historical per-op path: no region is ever segmented, no
        FusedRegion object constructed (test-enforced), plan snapshots
        unchanged. On: the planner stamps fusable regions (elementwise
        chains, reductions, scalar epilogues absorbed into their
        producer matmul/SpGEMM) after ``annotate_strategies``; the
        executor lowers each region under ONE annotate() dispatch
        frame with the epilogue pushed into the producing kernel's
        epilogue slot, the region-program seam can emit one jitted
        program per region, matmul_decisions records the boundary
        (est saved dispatches / HBM bytes), and MV111 verifies every
        stamp. The degradation ladder's rung 3 forces this off so a
        miscompiling fused region cannot survive retry.
      cse_enable: admission-time cross-query CSE
        (matrel_tpu/serve/mqo.py; docs/SERVING.md). Off (the default)
        no hoist is ever chosen (test-enforced) and a batch of one
        root is indifferent to it. On: a MultiPlan batch
        (``run_many`` / the admission worker's coalesced batches)
        detects interior subplans shared across its queries via the
        structural span keys, computes each exactly once, and feeds
        every consumer the result as an already-laid-out leaf (the
        result-cache interior-hit crediting, so ``infer_layout`` /
        ``comm_cost`` price the reuse); hoists happen only at fused-
        region boundaries (non-fusable kinds), so per-query epilogue
        chains keep fusing instead of being split. MV116 verifies the
        stamps; shared results flow into the result cache with
        transitive dep sets so rebind invalidation cascades. (Plan
        templates are no part of it: every session's plan lookup asks
        them on a plan-cache miss, ``session._plan_lookup``.)
      cse_min_uses: occurrence threshold for hoisting one shared
        interior (>= 2: a "shared" node used once is just the query
        itself). Occurrences are counted across the whole batch,
        within-query duplicates included.
      cse_template_max: entry bound on the plan-template cache every
        session keeps (LRU past it — a template is an affinity hint
        over the plan cache, never a correctness surface; eviction
        only costs a recompile).
      delta_patch_mode: how ``session.register_delta`` maintains
        dependent result-cache entries (serve/ivm.py; docs/IVM.md).
        "auto" (the default): patch when a delta rule applies AND the
        flop estimate (or a measured autotune ``ivm|`` winner, which
        overrides it) says the patch beats recompute — everything
        else falls back to the historical transitive kill. "force":
        patch every eligible entry regardless of pricing (test /
        bench forcing knob). "off": register_delta rebinds and kills
        like a plain register() — the escape hatch. Inert until
        register_delta is ever called: the default path constructs no
        delta objects and every cache key keeps its historical format
        (test-enforced bit-identity).
      delta_rank_max: largest factored rank a delta is worth keeping
        in thin ``U·Vᵀ`` form (ir/delta.py): a c-edge COO batch is
        exactly a rank-c update, and above this bound the thin
        products stop being thin — the delta then enters patches as
        its dense/sparse materialization (or prices out entirely).
      axis_cost_weights: per-mesh-axis relative inverse-bandwidth
        weights for the planner's comm model (core/mesh.MeshTopology):
        a collective leg over axis i is billed bytes × weights[i], so
        on a hierarchical ICI/DCN mesh the slow cross-slice axis is
        priced as expensive as it really is. The default (1.0, 1.0) is
        behaviour-preserving (every cost bit-identical to the flat
        model) AND doubles as "auto": when JAX exposes slice
        boundaries (device.slice_index on multi-slice TPU), the
        DCN-crossing axes are auto-weighted DCN_AXIS_WEIGHT. Setting
        anything ≠ (1.0, 1.0) is the calibration hook — it overrides
        detection (docs/TOPOLOGY.md).
      fleet_slices: multi-slice serving fleet (serve/fleet.py;
        docs/FLEET.md). 0 (the default) = off: no fleet objects are
        ever constructed and ``submit`` runs the historical
        single-controller pipeline bit-identically (test-enforced).
        >= 1 partitions the session mesh into that many serving
        slices (real ``device.slice_index`` boundaries when they
        match the count, contiguous virtual sub-meshes otherwise;
        degenerate shared-device slices when the mesh is too small),
        each with its own admission queue, worker, brownout state and
        slice-local result cache; ``session.submit`` routes each
        query through the fleet's placement policy.
      fleet_span_margin: placement bias toward slice-local execution:
        a query SPANS the whole mesh (one program over every slice,
        DCN-crossing collectives included) only when the byte model's
        estimated span cost is strictly below ``margin`` x the best
        slice-local estimate. 1.0 = neutral; < 1.0 demands a real
        win before paying DCN traffic (docs/FLEET.md placement
        derivation).
      fleet_directory_max: entry bound on the fleet's global
        structural-key directory (plan key -> owning slice). LRU past
        it — the directory is an affinity HINT, never a correctness
        surface, so eviction only costs a recompute.
      fleet_replicate_hits: remote-demand threshold for hot-entry
        replication: once a non-owning slice has taken this many
        directory hits on one key, the entry is replicated into it —
        priced and staged through the reshard planner under
        ``reshard_peak_budget_bytes`` (docs/FLEET.md migration
        pricing). 0 disables replication (directory hits still
        answer from the owning slice's cache).
      fleet_failover: dead/wedged-slice failover — a killed slice's
        queued entries re-admit onto surviving slices (deadlines and
        tenant attribution intact, refusals typed). Off = queued
        entries on a killed slice fail typed instead.
      fleet_placement_calibration: let the placement cost model read
        the drift auditor's calibration table
        (``drift_table_path``): per-(shape-class, backend, tier)
        measured ms/GFLOP + ms/MiB coefficients are consulted AHEAD
        of the analytic closed forms, provenance-stamped "measured"
        like autotune winners; classes with no calibration row fall
        back to the analytic model (docs/FLEET.md).
      obs_provenance: answer provenance ledger capacity (obs tier 4,
        docs/OBSERVABILITY.md). 0 (default) = off: zero ledger
        objects constructed, no lineage capture anywhere on the
        serve path (the brownout/breaker structural-zero contract).
        N > 0 keeps the last N per-answer lineage records in memory
        (``session.why()`` / ``python -m matrel_tpu why``) and emits
        each as a ``provenance`` event when the event log is on.
      obs_event_log_max_bytes: rotate the JSONL event log to a single
        ``.1`` sibling once it reaches this size. 0 (default) = never
        rotate (the historical unbounded-append behaviour,
        byte-identical). Readers stitch ``<log>.1`` + ``<log>``
        transparently, so rotation bounds the DISK while
        ``tail_bytes`` keeps bounding each read.
      lockdep_enable: runtime lock-order sanitizer
        (matrel_tpu/utils/lockdep.py; docs/CONCURRENCY.md). Off (the
        default) is bit-identical to the uninstrumented engine: the
        sanctioned lock constructors return raw threading primitives
        and ZERO lockdep objects are constructed (poisoned-init
        test-enforced, plan snapshots unchanged). On: every
        seam-constructed lock records per-thread acquisition stacks
        into a global lock-ORDER graph; inversions and
        held-across-dispatch violations are recorded as ``lockdep``
        obs events (and into the flight ring), rolled up by
        ``history --summary`` and fatal to ``--check``.
      lockdep_raise: escalate lockdep diagnostics from record-only to
        an immediate typed raise (LockOrderInversion /
        HeldAcrossDispatch) at the acquisition site — the race-drill
        and fixture-test mode. Requires ``lockdep_enable``.
      coeff_planner_enable: let the MAIN planner consult the drift
        auditor's calibrated ms/GFLOP + ms/MiB coefficients
        (parallel/coeffs.py — the seam; docs/COST_MODEL.md): strategy
        ranking and the chain DP's step cost price by measured ratios
        where every candidate has a warm row, falling back to the
        analytic closed forms otherwise; decisions are stamped
        ``cost: "measured"|"analytic"`` and plan-cache keys gain the
        ``coeffv:<epoch>|`` prefix so plans compiled under different
        coefficients never share a slot. Off (the default) is
        bit-identical: zero new objects, zero new key prefixes, zero
        new event fields (plan snapshots unchanged, test-enforced).
      coeff_min_samples: calibration rows below this sample count are
        treated as cold for planner ranking — a one-off measurement
        must not flip a strategy choice (the drift auditor's
        noise-band argument).
      coeff_replan_enable: close the loop (docs/COST_MODEL.md): a
        serve-side controller (serve/replan.py) watches the query
        event stream, and a firing DRIFT rank-order flag triggers a
        coefficient re-calibration + background re-planning of the
        affected cached plans under the new epoch — old plans keep
        serving, in-flight queries never block (the ``coeffv:``
        prefix). Requires ``coeff_planner_enable``. Off = zero
        controller objects (replan._CONSTRUCTED stays 0).
      coeff_replan_interval: queries between the controller's drift
        checks — the re-plan loop's cadence.
      coeff_replan_cooldown: checks a just-re-planned population sits
        out before its flags can fire again (hysteresis, the brownout
        dwell discipline): fresh samples under the NEW plans must
        accumulate before the loop may act on that population again,
        so a re-plan can never oscillate on its own stale evidence.
      spill_enable: the result cache's HBM → host RAM → disk spill
        hierarchy (serve/spill.py; docs/DURABILITY.md — the [P2]
        RDD-persist amortization rebuilt as explicit priced tiers).
        Off (the default) constructs ZERO spill objects and is
        bit-identical to the single-tier cache: LRU eviction drops
        entries exactly as before, plan snapshots unchanged
        (poisoned-init test-enforced, the brownout/breaker
        structural-zero contract). On: entries the byte budget evicts
        DEMOTE to a host-RAM numpy tier instead of dropping (and age
        host → disk under the host budget, as sha1-verified artifacts
        in ``state_dir`` — requires a result cache to spill FROM, so
        ``result_cache_max_bytes`` must be > 0, validated); a lower-
        tier hit THAWS the entry back to HBM paying only the priced
        transfer legs (parallel/coeffs.py ``spill:<leg>`` rows when
        the drift loop has calibrated them, analytic per-leg ms/MiB
        otherwise) — it never recomputes, and interior-substitution
        probes see the thawed entry as a laid-out leaf exactly like
        an HBM hit. Requires ``spill_enable`` for ``save_state()`` to
        persist result-cache entries (catalog/tables persist without
        it).
      spill_host_max_bytes: byte budget of the host-RAM tier. Past
        it, least-recently-used host entries age to disk when the
        disk tier exists (``state_dir`` set) AND the entry's hit
        count shows expected reuse (>= spill_disk_hits) — cold
        never-hit entries drop instead of paying disk IO on no
        evidence (docs/DURABILITY.md demotion policy).
      spill_disk_hits: minimum lifetime hit count an entry needs for
        the host tier to age it to DISK rather than drop it (the
        expected-reuse gate). 0 demotes everything the host tier
        evicts.
      state_dir: durable state directory — the disk spill tier
        (``<state_dir>/spill/`` sha1-verified artifacts) and the
        ``MatrelSession.save_state()``/``restore()`` snapshot root
        (``<state_dir>/state/`` checkpoint-format step dirs holding
        the catalog, the result-cache index with disk-tier entries by
        reference, the fleet directory, MQO template keys and the
        autotune/drift tables — docs/DURABILITY.md snapshot format).
        "" (the default) constructs nothing and disables the disk
        tier (host-only spill when spill_enable is on);
        ``save_state()``/``restore()`` then require an explicit
        directory argument.
    """

    block_size: int = 512
    mesh_shape: Optional[Tuple[int, int]] = None
    mesh_axis_names: Tuple[str, str] = ("x", "y")
    broadcast_threshold_bytes: int = 64 * 1024 * 1024
    strategy_override: str = "auto"
    sparsity_threshold: float = 0.05
    spgemm_density_threshold: float = 0.25
    spgemm_kernel_override: str = ""
    comm_alpha_bytes: float = 200_000.0
    default_dtype: str = "float32"
    matmul_precision: str = "highest"
    keep_input_dtype: bool = True
    use_pallas: bool = True
    pallas_interpret: bool = False
    chain_opt: bool = True
    rewrite_rules: bool = True
    donate_intermediates: bool = True
    join_pair_cap_entries: int = 1 << 26
    join_bruteforce_max_pairs: int = 1 << 28
    join_chunk_entries: int = 1 << 22
    plan_cache_max_plans: int = 64
    plan_cache_max_bytes: int = 4 << 30
    autotune: bool = False
    autotune_table_path: str = ""
    autotune_max_dim: int = 8192
    result_cache_max_bytes: int = 0
    result_cache_max_entries: int = 256
    serve_max_batch: int = 8
    serve_max_inflight: int = 2
    obs_level: str = "off"
    obs_event_log: str = ""
    obs_metrics_port: int = 0
    slo_targets: str = ""
    slo_fast_window_s: float = 60.0
    slo_slow_window_s: float = 1800.0
    slo_burn_threshold: float = 14.4
    slo_burn_exit: float = 1.0
    obs_flight_recorder: int = 0
    obs_flight_recorder_path: str = ""
    drift_table_path: str = ""
    verify_plans: str = "off"
    hbm_budget_bytes: int = 31 << 29
    reshard_peak_budget_bytes: int = 0
    axis_cost_weights: Tuple[float, float] = (1.0, 1.0)
    fault_inject: str = ""
    fault_inject_seed: int = 0
    retry_max_attempts: int = 0
    retry_backoff_ms: float = 25.0
    retry_backoff_mult: float = 2.0
    retry_jitter: float = 0.5
    deadline_ms: float = 0.0
    serve_queue_max: int = 0
    serve_tenant_weights: str = ""
    serve_tenant_queue_max: int = 0
    brownout_enable: bool = False
    brownout_window: int = 32
    brownout_dwell: int = 8
    brownout_wait_high_ms: float = 200.0
    brownout_wait_low_ms: float = 50.0
    brownout_depth_high: int = 64
    brownout_depth_low: int = 8
    brownout_miss_high: float = 0.25
    brownout_miss_low: float = 0.05
    breaker_threshold: int = 0
    breaker_cooldown_ms: float = 1000.0
    breaker_half_open_probes: int = 1
    precision_sla: str = "default"
    precision_enable_bf16: bool = True
    precision_enable_int: bool = True
    fusion_enable: bool = False
    cse_enable: bool = False
    cse_min_uses: int = 2
    cse_template_max: int = 64
    delta_patch_mode: str = "auto"
    delta_rank_max: int = 512
    fleet_slices: int = 0
    fleet_span_margin: float = 1.0
    fleet_directory_max: int = 4096
    fleet_replicate_hits: int = 3
    fleet_failover: bool = True
    fleet_placement_calibration: bool = True
    obs_provenance: int = 0
    obs_event_log_max_bytes: int = 0
    lockdep_enable: bool = False
    lockdep_raise: bool = False
    coeff_planner_enable: bool = False
    coeff_min_samples: int = 3
    coeff_replan_enable: bool = False
    coeff_replan_interval: int = 32
    coeff_replan_cooldown: int = 2
    spill_enable: bool = False
    spill_host_max_bytes: int = 2 << 30
    spill_disk_hits: int = 1
    state_dir: str = ""

    def __post_init__(self):
        # enablement is "anything != off", so an unvalidated typo/case
        # variant ("OFF", "of") would silently switch the production
        # query path onto the instrumented one — reject it at
        # construction (case-insensitively normalised)
        level = self.obs_level.lower()
        if level not in ("off", "on", "analyze"):
            raise ValueError(
                f"obs_level must be one of 'off'/'on'/'analyze', "
                f"got {self.obs_level!r}")
        object.__setattr__(self, "obs_level", level)
        # same typo hazard, opposite failure mode: a misspelled "eror"
        # would silently DISABLE the verifier's raise and ship the very
        # infeasible plan it exists to block
        vp = self.verify_plans.lower()
        if vp not in ("off", "warn", "error"):
            raise ValueError(
                f"verify_plans must be one of 'off'/'warn'/'error', "
                f"got {self.verify_plans!r}")
        object.__setattr__(self, "verify_plans", vp)
        # live telemetry plane (docs/OBSERVABILITY.md tier 3): an
        # out-of-range port would surface only as an OSError at the
        # first session construction; a malformed SLO spec must fail
        # HERE (the fault_inject/tenant-weights precedent — silently
        # monitoring nothing while the operator believes objectives
        # are in force is the worst failure an SLO knob can have);
        # un-separated burn thresholds would flap alerts on every
        # sample (the brownout hysteresis argument)
        if not (0 <= self.obs_metrics_port <= 65535):
            raise ValueError(
                f"obs_metrics_port must be a port in [0, 65535] "
                f"(0 disables the endpoint), "
                f"got {self.obs_metrics_port!r}")
        if self.slo_targets:
            parse_slo_targets(self.slo_targets)
        if not (0.0 < self.slo_fast_window_s < self.slo_slow_window_s):
            raise ValueError(
                "slo windows need 0 < slo_fast_window_s < "
                "slo_slow_window_s, got "
                f"({self.slo_fast_window_s!r}, "
                f"{self.slo_slow_window_s!r})")
        if not (0.0 < self.slo_burn_exit < self.slo_burn_threshold):
            raise ValueError(
                "slo burn thresholds need 0 < slo_burn_exit < "
                "slo_burn_threshold (the hysteresis separation), got "
                f"({self.slo_burn_exit!r}, "
                f"{self.slo_burn_threshold!r})")
        # a negative ring capacity would silently build a deque with
        # maxlen=None — an UNBOUNDED recorder, the opposite of the
        # always-cheap contract — reject it at construction
        if self.obs_flight_recorder < 0:
            raise ValueError(
                f"obs_flight_recorder must be >= 0 (ring capacity; "
                f"0 disables), got {self.obs_flight_recorder!r}")
        # a zero/negative admission width or in-flight bound would
        # deadlock the serve pipeline's coalescing loop (it always
        # admits at least the query it popped) — reject at construction
        if self.result_cache_max_entries < 1:
            raise ValueError(
                f"result_cache_max_entries must be >= 1, "
                f"got {self.result_cache_max_entries!r}")
        if self.serve_max_batch < 1:
            raise ValueError(
                f"serve_max_batch must be >= 1, got {self.serve_max_batch!r}")
        if self.serve_max_inflight < 1:
            raise ValueError(
                f"serve_max_inflight must be >= 1, "
                f"got {self.serve_max_inflight!r}")
        # a zero/negative weight would make an axis FREE (or negative)
        # and silently route every collective onto it; a 3-tuple would
        # desync from the 2D grid — reject both at construction. The
        # normalised float tuple is what every cache key embeds.
        w = tuple(self.axis_cost_weights)
        if len(w) != 2 or not all(
                isinstance(v, (int, float)) and v > 0.0 for v in w):
            raise ValueError(
                "axis_cost_weights must be two positive numbers "
                f"(per mesh axis), got {self.axis_cost_weights!r}")
        object.__setattr__(self, "axis_cost_weights",
                           (float(w[0]), float(w[1])))
        # resilience knobs: a malformed fault spec must fail HERE, not
        # silently inject nothing while a chaos test believes it is
        # injecting (the obs_level typo precedent); negative retry /
        # backoff / deadline values have no meaning and would corrupt
        # the backoff arithmetic silently
        if self.fault_inject:
            from matrel_tpu.resilience.faults import parse_spec
            parse_spec(self.fault_inject)
        if self.retry_max_attempts < 0:
            raise ValueError(
                f"retry_max_attempts must be >= 0, "
                f"got {self.retry_max_attempts!r}")
        if self.retry_backoff_ms < 0 or self.retry_backoff_mult < 1.0 \
                or not (0.0 <= self.retry_jitter <= 1.0):
            raise ValueError(
                "retry backoff needs retry_backoff_ms >= 0, "
                "retry_backoff_mult >= 1, retry_jitter in [0, 1]; got "
                f"({self.retry_backoff_ms!r}, "
                f"{self.retry_backoff_mult!r}, {self.retry_jitter!r})")
        if self.deadline_ms < 0:
            raise ValueError(
                f"deadline_ms must be >= 0 (0 disables), "
                f"got {self.deadline_ms!r}")
        # a negative reshard budget has no meaning — and would silently
        # read as "unbounded" in every fits() check while the caller
        # believes a cap is in force (the obs_level typo precedent)
        if self.reshard_peak_budget_bytes < 0:
            raise ValueError(
                f"reshard_peak_budget_bytes must be >= 0 (0 = legacy "
                f"single-shot reshards), "
                f"got {self.reshard_peak_budget_bytes!r}")
        if self.serve_queue_max < 0:
            raise ValueError(
                f"serve_queue_max must be >= 0 (0 = unbounded), "
                f"got {self.serve_queue_max!r}")
        # overload control plane (docs/OVERLOAD.md): a malformed tenant
        # weight spec must fail at construction (the fault_inject
        # precedent) — silently weighting nothing while the operator
        # believes fairness is in force is the worst failure mode a
        # fairness knob can have
        if self.serve_tenant_weights:
            parse_tenant_weights(self.serve_tenant_weights)
        if self.serve_tenant_queue_max < 0:
            raise ValueError(
                f"serve_tenant_queue_max must be >= 0 (0 = no "
                f"per-tenant cap), got {self.serve_tenant_queue_max!r}")
        # brownout hysteresis NEEDS separated thresholds: low == high
        # would flap the rung on every sample and low > high would
        # deadlock the ladder (enter and exit both impossible)
        if self.brownout_window < 1 or self.brownout_dwell < 1:
            raise ValueError(
                "brownout_window and brownout_dwell must be >= 1; got "
                f"({self.brownout_window!r}, {self.brownout_dwell!r})")
        for name, lo, hi in (
                ("wait", self.brownout_wait_low_ms,
                 self.brownout_wait_high_ms),
                ("depth", self.brownout_depth_low,
                 self.brownout_depth_high),
                ("miss", self.brownout_miss_low,
                 self.brownout_miss_high)):
            if not (0 <= lo < hi):
                raise ValueError(
                    f"brownout_{name} thresholds need 0 <= low < high "
                    f"(the hysteresis separation), got ({lo!r}, {hi!r})")
        if not (0.0 <= self.brownout_miss_high <= 1.0):
            raise ValueError(
                f"brownout_miss_high must be a rate in [0, 1], "
                f"got {self.brownout_miss_high!r}")
        if self.breaker_threshold < 0:
            raise ValueError(
                f"breaker_threshold must be >= 0 (0 disables "
                f"breakers), got {self.breaker_threshold!r}")
        if self.breaker_cooldown_ms <= 0 \
                or self.breaker_half_open_probes < 1:
            raise ValueError(
                "breakers need breaker_cooldown_ms > 0 and "
                "breaker_half_open_probes >= 1; got "
                f"({self.breaker_cooldown_ms!r}, "
                f"{self.breaker_half_open_probes!r})")
        # the SLA vocabulary gates NUMERICS, not just performance: an
        # unvalidated typo ("fasst") would silently run the default
        # path while the caller believes a bound was requested — or
        # worse, a misspelled "exact" would tier DOWN. Reject at
        # construction (case-insensitive, "bf16" normalised).
        object.__setattr__(self, "precision_sla",
                           normalize_sla(self.precision_sla))
        # IVM knobs (docs/IVM.md): a typo'd mode ("of", "forced")
        # would silently run "auto" while the operator believes the
        # ladder's escape hatch is in force — the obs_level precedent;
        # a non-positive rank bound would disable the factored form
        # while reading as "unlimited"
        mode = self.delta_patch_mode.lower()
        if mode not in ("auto", "force", "off"):
            raise ValueError(
                f"delta_patch_mode must be one of 'auto'/'force'/"
                f"'off', got {self.delta_patch_mode!r}")
        object.__setattr__(self, "delta_patch_mode", mode)
        if self.delta_rank_max < 1:
            raise ValueError(
                f"delta_rank_max must be >= 1, "
                f"got {self.delta_rank_max!r}")
        # multi-query-optimization knobs (docs/SERVING.md): a
        # min_uses of 1 would hoist EVERY interior of every batch —
        # pure overhead read as "more sharing"; a zero template bound
        # would evict each template at insert and turn steady-state
        # rebind traffic into a permanent recompile while the
        # operator believes templates are in force
        if self.cse_min_uses < 2:
            raise ValueError(
                f"cse_min_uses must be >= 2 (an interior used once "
                f"is not shared), got {self.cse_min_uses!r}")
        if self.cse_template_max < 1:
            raise ValueError(
                f"cse_template_max must be >= 1, "
                f"got {self.cse_template_max!r}")
        # same hazard for the kernel forcing knob: a typo'd override
        # would surface only as a mid-traffic ValueError on the first
        # dispatching query — or never, while the operator believes
        # the knob is in force. Validated against the vocabulary tuple
        # (the PRECISION_SLAS precedent — config cannot import the
        # registry, which needs jax; test_kernel_registry pins the
        # tuple == the registry's actual ids).
        if (self.spgemm_kernel_override
                and self.spgemm_kernel_override not in
                SPGEMM_KERNEL_IDS):
            raise ValueError(
                f"spgemm_kernel_override must be one of "
                f"{SPGEMM_KERNEL_IDS} (or '' to disable), got "
                f"{self.spgemm_kernel_override!r}")
        # fleet knobs (docs/FLEET.md): a negative slice count would
        # silently read as "off" while the operator believes a fleet
        # is serving (the obs_level typo precedent); a non-positive
        # span margin makes spanning unreachable while reading as
        # "neutral"; a zero directory bound would evict every
        # ownership record at insert and turn the hit-anywhere
        # protocol into a permanent miss
        if self.fleet_slices < 0:
            raise ValueError(
                f"fleet_slices must be >= 0 (0 disables the fleet), "
                f"got {self.fleet_slices!r}")
        if self.fleet_span_margin <= 0:
            raise ValueError(
                f"fleet_span_margin must be > 0, "
                f"got {self.fleet_span_margin!r}")
        if self.fleet_directory_max < 1:
            raise ValueError(
                f"fleet_directory_max must be >= 1, "
                f"got {self.fleet_directory_max!r}")
        if self.fleet_replicate_hits < 0:
            raise ValueError(
                f"fleet_replicate_hits must be >= 0 (0 disables "
                f"hot-entry replication), "
                f"got {self.fleet_replicate_hits!r}")
        # obs tier 4 (docs/OBSERVABILITY.md): a negative ledger
        # capacity would silently read as "off" while the operator
        # believes lineage is being captured (the fleet_slices
        # precedent); a negative rotation threshold likewise reads as
        # "never rotate" while the operator believes the disk is
        # bounded
        if self.obs_provenance < 0:
            raise ValueError(
                f"obs_provenance must be >= 0 (0 disables the "
                f"provenance ledger), got {self.obs_provenance!r}")
        if self.obs_event_log_max_bytes < 0:
            raise ValueError(
                f"obs_event_log_max_bytes must be >= 0 (0 disables "
                f"event-log rotation), "
                f"got {self.obs_event_log_max_bytes!r}")
        # concurrency sanitizer (docs/CONCURRENCY.md): lockdep_raise
        # without lockdep_enable would silently raise NOTHING while
        # the drill operator believes violations are fatal (the
        # obs_level typo precedent — a sanitizer that monitors
        # nothing while believed armed is its worst failure mode)
        if self.lockdep_raise and not self.lockdep_enable:
            raise ValueError(
                "lockdep_raise requires lockdep_enable (a raise mode "
                "with no instrumentation in force would silently "
                "check nothing)")
        # cost-model loop knobs (docs/COST_MODEL.md): a re-plan
        # controller with no coefficient-consulting planner would
        # re-calibrate a table nothing reads (the lockdep_raise
        # dependency precedent); degenerate cadence/sample bounds
        # would spin the check loop or let one noisy sample flip
        # strategy rankings
        if self.coeff_min_samples < 1:
            raise ValueError(
                f"coeff_min_samples must be >= 1, "
                f"got {self.coeff_min_samples!r}")
        if self.coeff_replan_enable and not self.coeff_planner_enable:
            raise ValueError(
                "coeff_replan_enable requires coeff_planner_enable "
                "(re-planning recalibrates coefficients the planner "
                "would otherwise never consult)")
        if self.coeff_replan_interval < 1:
            raise ValueError(
                f"coeff_replan_interval must be >= 1, "
                f"got {self.coeff_replan_interval!r}")
        if self.coeff_replan_cooldown < 0:
            raise ValueError(
                f"coeff_replan_cooldown must be >= 0, "
                f"got {self.coeff_replan_cooldown!r}")
        # durability knobs (docs/DURABILITY.md): a spill hierarchy
        # under a DISABLED result cache would demote nothing while the
        # operator believes the working set extends past HBM (the
        # lockdep_raise dependency precedent); a non-positive host
        # budget would bounce every demotion straight to disk/drop
        # while reading as "host tier in force"
        if self.spill_enable and self.result_cache_max_bytes <= 0:
            raise ValueError(
                "spill_enable requires result_cache_max_bytes > 0 "
                "(the spill hierarchy extends the result cache — with "
                "the cache off there is nothing to demote)")
        if self.spill_host_max_bytes < 1:
            raise ValueError(
                f"spill_host_max_bytes must be >= 1, "
                f"got {self.spill_host_max_bytes!r}")
        if self.spill_disk_hits < 0:
            raise ValueError(
                f"spill_disk_hits must be >= 0 (0 ages everything "
                f"the host tier evicts), got {self.spill_disk_hits!r}")

    def replace(self, **kw: Any) -> "MatrelConfig":
        return dataclasses.replace(self, **kw)

    @staticmethod
    def from_env(base: Optional["MatrelConfig"] = None) -> "MatrelConfig":
        """Build a config from MATREL_* environment variables."""
        cfg = base or MatrelConfig()
        overrides: dict = {}
        for f in dataclasses.fields(MatrelConfig):
            env_key = "MATREL_" + f.name.upper()
            if env_key not in os.environ:
                continue
            raw = os.environ[env_key]
            if f.type in ("int", int):
                overrides[f.name] = int(raw)
            elif f.type in ("float", float):
                overrides[f.name] = float(raw)
            elif f.type in ("bool", bool):
                overrides[f.name] = raw.lower() in ("1", "true", "yes", "on")
            elif f.name == "mesh_shape":
                parts = [int(p) for p in raw.replace("x", ",").split(",") if p]
                overrides[f.name] = tuple(parts)
            elif f.name == "axis_cost_weights":
                parts = [float(p)
                         for p in raw.replace("x", ",").split(",") if p]
                overrides[f.name] = tuple(parts)
            else:
                overrides[f.name] = raw
        return cfg.replace(**overrides) if overrides else cfg

    @staticmethod
    def from_dict(d: Mapping[str, Any], base: Optional["MatrelConfig"] = None) -> "MatrelConfig":
        cfg = base or MatrelConfig()
        valid = {f.name for f in dataclasses.fields(MatrelConfig)}
        unknown = set(d) - valid
        if unknown:
            raise KeyError(f"unknown MatrelConfig keys: {sorted(unknown)}")
        return cfg.replace(**dict(d))


#: The per-query accuracy-SLA vocabulary (docs/PRECISION.md): named
#: levels plus the explicit-dtype spellings that pin one tier.
PRECISION_SLAS = ("default", "exact", "high", "fast",
                  "float32", "bfloat16", "bf16x3", "int32", "int8")

#: The SpGEMM kernel-registry vocabulary (docs/SPARSE_KERNELS.md) —
#: what ``spgemm_kernel_override`` validates against at construction.
#: Config cannot import ops/kernel_registry (it needs jax), so the
#: tuple lives here and test_kernel_registry pins it equal to the
#: registry's actual ids; registering a new kernel extends BOTH.
SPGEMM_KERNEL_IDS = ("xla_gather", "pallas_generic", "pallas_band",
                     "pallas_cluster", "pallas_powerlaw")


def normalize_sla(sla) -> str:
    """Validate + normalise one precision-SLA value (config field or
    per-query ``precision=`` argument). None → "default"."""
    if sla is None:
        return "default"
    s = str(sla).lower().strip()
    if s in ("bf16", "bfloat16"):
        s = "bfloat16"
    if s == "f32":
        s = "float32"
    if s not in PRECISION_SLAS:
        raise ValueError(
            f"precision SLA must be one of {PRECISION_SLAS} (or 'bf16'/"
            f"'f32' aliases), got {sla!r}")
    return s


def parse_tenant_weights(spec) -> dict:
    """Validate + parse a ``serve_tenant_weights`` spec
    (``"gold:4,silver:2,bronze:1"``) into ``{tenant: float weight}``.
    Empty/None → {} (one implicit tenant, the historical FIFO).
    Raises ``ValueError`` on empty names, duplicate names, or
    non-positive weights — config.__post_init__ calls this so a typo
    fails at construction (the fault_inject precedent)."""
    if not spec:
        return {}
    out: dict = {}
    for part in (p.strip() for p in str(spec).split(",")):
        if not part:
            continue
        name, sep, w = part.partition(":")
        name = name.strip()
        if not sep or not name:
            raise ValueError(
                f"serve_tenant_weights entry {part!r} must be "
                f"'name:weight'")
        if name in out:
            raise ValueError(
                f"serve_tenant_weights names tenant {name!r} twice")
        try:
            weight = float(w)
        except ValueError:
            raise ValueError(
                f"serve_tenant_weights weight {w!r} (tenant "
                f"{name!r}) is not a number") from None
        if not weight > 0.0:
            raise ValueError(
                f"serve_tenant_weights weight for {name!r} must be "
                f"> 0, got {weight!r}")
        out[name] = weight
    if not out:
        raise ValueError(
            f"serve_tenant_weights {spec!r} names no tenants")
    return out


#: The SLO objective vocabulary (docs/OBSERVABILITY.md tier 3):
#: latency targets at named quantiles (milliseconds) plus availability.
SLO_OBJECTIVES = ("avail", "p50_ms", "p90_ms", "p95_ms", "p99_ms")


def parse_slo_targets(spec) -> dict:
    """Validate + parse an ``slo_targets`` spec
    (``"gold:p95_ms=50,avail=0.999;bronze:avail=0.99"``) into
    ``{tenant: {objective: float target}}``. Empty/None → {} (no
    objectives, no monitors). Raises ``ValueError`` on unknown
    objectives, duplicate tenants, availability targets outside (0, 1)
    or non-positive latency targets — config.__post_init__ calls this
    so a typo fails at construction (the tenant-weights precedent)."""
    if not spec:
        return {}
    out: dict = {}
    for tpart in (p.strip() for p in str(spec).split(";")):
        if not tpart:
            continue
        tenant, sep, objs = tpart.partition(":")
        tenant = tenant.strip()
        if not sep or not tenant:
            raise ValueError(
                f"slo_targets entry {tpart!r} must be "
                f"'tenant:objective=target[,objective=target...]'")
        if tenant in out:
            raise ValueError(
                f"slo_targets names tenant {tenant!r} twice")
        targets: dict = {}
        for opart in (p.strip() for p in objs.split(",")):
            if not opart:
                continue
            obj, osep, val = opart.partition("=")
            obj = obj.strip()
            if not osep or obj not in SLO_OBJECTIVES:
                raise ValueError(
                    f"slo_targets objective {opart!r} (tenant "
                    f"{tenant!r}) must be one of {SLO_OBJECTIVES} "
                    f"with '=target'")
            if obj in targets:
                raise ValueError(
                    f"slo_targets names objective {obj!r} twice for "
                    f"tenant {tenant!r}")
            try:
                target = float(val)
            except ValueError:
                raise ValueError(
                    f"slo_targets target {val!r} (tenant {tenant!r}, "
                    f"objective {obj!r}) is not a number") from None
            if obj == "avail":
                if not (0.0 < target < 1.0):
                    raise ValueError(
                        f"slo_targets avail target for {tenant!r} "
                        f"must be in (0, 1), got {target!r}")
            elif not target > 0.0:
                raise ValueError(
                    f"slo_targets latency target {obj} for "
                    f"{tenant!r} must be > 0 ms, got {target!r}")
            targets[obj] = target
        if not targets:
            raise ValueError(
                f"slo_targets entry {tpart!r} declares no objectives")
        out[tenant] = targets
    if not out:
        raise ValueError(f"slo_targets {spec!r} names no tenants")
    return out


_default_config = MatrelConfig.from_env()


def default_config() -> MatrelConfig:
    return _default_config


def set_default_config(cfg: MatrelConfig) -> None:
    global _default_config
    _default_config = cfg


def on_tpu() -> bool:
    """Is the default backend a TPU — the one place that answers it."""
    import jax
    return jax.default_backend() == "tpu"


def configure_compile_cache() -> str:
    """Place JAX's persistent compilation cache and return its
    directory. Where JAX_COMPILATION_CACHE_DIR is set JAX already uses
    it and no other directory is set in code; where it is not, the
    cache is ``<checkout>/.jax_cache`` — a fixed path (the path is part
    of the cache key, so a directory that moves never hits). Called by
    MatrelSession.__init__ and by the root scripts before their first
    compile; idempotent. From the first call on the process also hears
    what jax says of its compiles and of this cache's hits and misses
    (obs.trace.hear_jax: the cold ring's ``jit.*`` records)."""
    import jax
    from matrel_tpu.obs import trace as trace_lib
    trace_lib.hear_jax()
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            ".jax_cache")
        if jax.config.jax_compilation_cache_dir != path:
            jax.config.update("jax_compilation_cache_dir", path)
    return path


def pallas_enabled(config: "MatrelConfig" = None) -> bool:
    """True when hand-written Pallas kernels should run: the config
    toggle is on AND the backend is a real TPU (CPU keeps the XLA
    paths), OR pallas_interpret forces them in interpret mode for
    testing. The single gate shared by every compact-executor call
    site; pair with ``pallas_interpret_mode`` for the interpret flag."""
    cfg = config or default_config()
    if not cfg.use_pallas:
        return False
    return on_tpu() or cfg.pallas_interpret


def pallas_interpret_mode(config: "MatrelConfig" = None) -> bool:
    """interpret= flag for pallas_call at the shared call sites: True
    only when the compact paths were forced onto a non-TPU backend."""
    cfg = config or default_config()
    return cfg.pallas_interpret and not on_tpu()


def resolve_interpret(interpret, config: "MatrelConfig" = None) -> bool:
    """The single None→config resolver for per-call ``interpret``
    parameters across every Pallas call site (ops/pallas_spmv.py,
    ops/spmm.py, workloads/pagerank.py): an explicit True/False wins;
    None defers to pallas_interpret_mode."""
    if interpret is not None:
        return bool(interpret)
    return pallas_interpret_mode(config)
