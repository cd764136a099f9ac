"""matlint — AST-based custom linter for this codebase's own hazard
classes (the static-analysis layer's source-level half; the plan-level
half is matrel_tpu/analysis/).

Generic linters cannot know that a ``block_until_ready`` inside the
executor's lowering is a query-hot-path sync regression, that a
``to_dense`` inside a sparse dispatch module silently voids the SpGEMM
no-densify guarantee, or that a ``shard_map`` without explicit
``out_specs`` leaves the collective contract implicit. Each of those
has bitten (or nearly bitten) a past round; matlint pins them.

Usage:
    python tools/matlint.py                # default scan set, rc 1 on findings
    python tools/matlint.py path1 path2    # explicit files/dirs
    python tools/matlint.py --list-rules   # rule catalogue

Suppression: append ``# matlint: disable=ML001`` (comma-separated for
several codes) to the line where the flagged call STARTS, with a
justification in the same comment. Suppressions are deliberate,
reviewable exceptions — the repo-wide run (``make lint``,
tests/test_matlint.py) stays green only through them.

Rule catalogue (each rule's class docstring is the authority):
  ML001  host-sync call in lowering-path modules
  ML002  to_dense/todense inside a sparse dispatch module
  ML003  shard_map call without explicit out_specs
  ML004  direct MatrelConfig() construction inside the package
  ML005  cache dict keyed by sharding-spec-ish values
  ML006  raw wall-clock timing in library code outside obs/
  ML007  bare/broad except that silently swallows and continues
  ML008  layout-changing jax.device_put in lowering modules
  ML009  Pallas kernel defined outside ops/kernel_registry.py in
         executor-reachable ops modules (the "one seam" rule)
  ML010  jax.jit call site outside the executor's region-emission
         seam (executor.py) and utils/ — jitted-program emission is
         one compilation seam (the ML009 idiom for programs)
  ML011  unbounded-queue growth idiom: deque()/queue.Queue() without
         a bound in matrel_tpu/serve/, or threading.Thread without
         an explicit daemon= anywhere in the package
  ML012  ResultCache entry payloads mutated outside the sanctioned
         patch/apply seam in serve/result_cache.py (the ML009/ML010
         one-seam idiom applied to cached state)
  ML013  ad-hoc timing accumulation (append/extend onto latency-named
         lists) in matrel_tpu/ outside obs/ — timing metrics flow
         through the registry's sketch/histogram API so live and
         offline quantiles share one definition
  ML014  cross-slice result-cache mutation outside the fleet API
         (serve/fleet.py) — another slice's cache mutates only
         through the directory/replication seam
  ML015  provenance stamp written outside the answer ledger's
         sanctioned writers (obs/provenance.py) — lineage stores are
         one seam so MV115 can trust what it cross-checks
  ML016  template/CSE cache keyed by identity or spec values
         (id()/.uid/.spec/.sharding) instead of the canonical
         structural key — the ML005 hazard extended to the
         multi-query-optimization plane (serve/mqo.py)
  ML017  bare threading.Lock()/RLock() construction outside the
         utils/lockdep.py seam — locks are named, inventoried and
         lockdep-swappable only when built through make_lock/
         make_rlock (the ML009/ML010 one-seam idiom applied to
         locks; docs/CONCURRENCY.md)
  ML018  raw drift-table read (drift.load_table) in planner/serve
         code outside the parallel/coeffs.py seam — coefficient
         consults flow through one memoized, epoch-stamped reader so
         every consumer ranks by the SAME table state and plan keys
         shatter exactly when decisions could change
         (docs/COST_MODEL.md)
  ML019  raw file IO (open/np.save/np.load/json.dump/os.replace) in
         matrel_tpu/serve/ outside the spill/checkpoint seam
         (serve/spill.py) — durable serving state goes through ONE
         writer so every artifact is sha1-stamped, atomically
         renamed, and readable by the robust restore path; an ad-hoc
         write is invisible to save_state and unverifiable on thaw
         (docs/DURABILITY.md)
"""

from __future__ import annotations

import ast
import dataclasses
import os
import re
import sys
from typing import Iterator, List, Optional, Sequence

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: Default scan set for ``make lint`` / the repo-clean test. tests/ is
#: excluded by design: tests legitimately poke every hazard (poisoned
#: to_dense spies, sync-forcing fixtures) and carry their own review.
DEFAULT_PATHS = ("matrel_tpu", "tools", "examples", "chip_smoke.py")

_SUPPRESS_RE = re.compile(r"#\s*matlint:\s*disable=([A-Za-z0-9_,\s]+)")


@dataclasses.dataclass(frozen=True)
class Finding:
    path: str
    line: int
    rule: str
    message: str

    def render(self) -> str:
        return f"{self.path}:{self.line}: {self.rule} {self.message}"


def _rel(path: str) -> str:
    try:
        return os.path.relpath(path, REPO)
    except ValueError:
        return path


def _call_name(func: ast.AST) -> str:
    """Dotted tail of a call target: ``jax.block_until_ready`` ->
    "jax.block_until_ready", ``x.to_dense`` -> ".to_dense"."""
    if isinstance(func, ast.Name):
        return func.id
    if isinstance(func, ast.Attribute):
        base = _call_name(func.value)
        return (base + "." if base else ".") + func.attr
    return ""


class Rule:
    """One hazard class. ``applies_to`` scopes the MODULE set (the
    hazard is contextual — the same call is fine elsewhere); ``check``
    yields findings for one parsed file."""

    id: str = "ML000"

    def applies_to(self, relpath: str) -> bool:
        return True

    def check(self, tree: ast.Module, relpath: str) -> Iterator[Finding]:
        raise NotImplementedError


#: Modules whose code runs on (or traces into) the query hot path —
#: the executor's lowering, the strategy kernels, the ops kernels, the
#: IR/relational lowerings. A host sync here stalls every query.
_LOWERING_MODULES = re.compile(
    r"^matrel_tpu/(executor\.py|ops/|parallel/strategies\.py|"
    r"relational/|ir/)")


class HostSyncRule(Rule):
    """ML001: host-synchronising calls in lowering-path modules.

    ``block_until_ready``/``jax.device_get`` force a device round-trip;
    on the query hot path that serialises the pipeline the whole
    one-compiled-program design exists to avoid (the obs_level="off"
    contract: zero extra syncs — tests/test_obs.py enforces it
    dynamically for the executor, this rule pins it statically for
    every lowering module). ``np.asarray`` inside a Lowerer method is
    the same hazard wearing numpy clothes — on a traced value it
    either syncs or raises — unless it sits under
    ``jax.ensure_compile_time_eval()`` (host-side metadata work, the
    sanctioned idiom). The ONE legitimate sync — the analyze-mode
    op_hook in executor.py, guarded by ``self.op_hook is not None`` —
    carries the inline suppression this rule's docstring mandates."""

    id = "ML001"
    _SYNC_TAILS = ("block_until_ready", "device_get")

    def applies_to(self, relpath: str) -> bool:
        return bool(_LOWERING_MODULES.match(relpath))

    def check(self, tree, relpath):
        # (node, inside_lowerer_class, under_compile_time_eval)
        stack: List[tuple] = [(tree, False, False)]
        while stack:
            node, in_lowerer, under_cte = stack.pop()
            if isinstance(node, ast.ClassDef):
                in_lowerer = in_lowerer or node.name.endswith("Lowerer")
            if isinstance(node, ast.With):
                for item in node.items:
                    name = _call_name(item.context_expr.func) if \
                        isinstance(item.context_expr, ast.Call) else ""
                    if name.endswith("ensure_compile_time_eval"):
                        under_cte = True
            if isinstance(node, ast.Call):
                name = _call_name(node.func)
                tail = name.rsplit(".", 1)[-1]
                if tail in self._SYNC_TAILS:
                    yield Finding(relpath, node.lineno, self.id,
                                  f"host sync `{name}` on a "
                                  "lowering path — stalls every query "
                                  "(obs_level='off' contract)")
                elif (tail == "asarray" and in_lowerer
                        and not under_cte
                        and name.split(".", 1)[0] in ("np", "numpy")):
                    yield Finding(
                        relpath, node.lineno, self.id,
                        "np.asarray inside a Lowerer method outside "
                        "jax.ensure_compile_time_eval() — syncs or "
                        "raises on traced values")
            for child in ast.iter_child_nodes(node):
                stack.append((child, in_lowerer, under_cte))


class NoDensifyRule(Rule):
    """ML002: ``to_dense``/``todense`` inside a sparse dispatch module.

    matrel_tpu/ops/ holds the kernels whose whole reason to exist is
    NOT materialising dense forms (SpGEMM's no-densify guarantee is
    asserted dynamically by test_spgemm's poisoned-to_dense spy; the
    verifier's MV104 pins the dispatch side). A densify call added to
    one of these modules is either a bug or a fallback that belongs in
    the executor's dispatch, where the planner can see and price it."""

    id = "ML002"
    _TAILS = ("to_dense", "todense")

    def applies_to(self, relpath: str) -> bool:
        return relpath.startswith("matrel_tpu/ops/")

    def check(self, tree, relpath):
        for node in ast.walk(tree):
            if isinstance(node, ast.Call):
                tail = _call_name(node.func).rsplit(".", 1)[-1]
                if tail in self._TAILS:
                    yield Finding(
                        relpath, node.lineno, self.id,
                        f"`{tail}` inside a sparse dispatch module — "
                        "densify fallbacks belong in the executor "
                        "dispatch where the planner prices them")


class ShardMapOutSpecsRule(Rule):
    """ML003: ``shard_map`` without explicit ``out_specs``.

    The out_spec IS the collective contract: it decides whether the
    runtime all-gathers, leaves shards in place, or replicates — and an
    implicit/defaulted one makes the comm cost invisible to review and
    to the planner's byte model. Every call must say what it emits
    (the compat shim that forwards kwargs is exempt)."""

    id = "ML003"

    def applies_to(self, relpath: str) -> bool:
        return relpath != "matrel_tpu/utils/compat.py"

    def check(self, tree, relpath):
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            if _call_name(node.func).rsplit(".", 1)[-1] != "shard_map":
                continue
            has_kw = any(k.arg == "out_specs" for k in node.keywords)
            # positional form: shard_map(f, mesh, in_specs, out_specs)
            if not has_kw and len(node.args) < 4:
                yield Finding(
                    relpath, node.lineno, self.id,
                    "shard_map without explicit out_specs — the "
                    "collective contract must be stated at the call "
                    "site")


class ConfigFlowRule(Rule):
    """ML004: direct ``MatrelConfig(...)`` construction inside the
    package.

    Library code must consume the config that FLOWS to it (a ``config``
    parameter defaulting through ``default_config()``) — a fresh
    ``MatrelConfig()`` silently discards every session/env override the
    caller set (the round-2 class of bug where a module ran with
    default thresholds while the session was configured otherwise).
    Construction is for entry points: config.py itself, tests, and the
    bench/tool harnesses outside the package."""

    id = "ML004"

    def applies_to(self, relpath: str) -> bool:
        return (relpath.startswith("matrel_tpu/")
                and relpath != "matrel_tpu/config.py")

    def check(self, tree, relpath):
        for node in ast.walk(tree):
            if isinstance(node, ast.Call):
                tail = _call_name(node.func).rsplit(".", 1)[-1]
                if tail == "MatrelConfig":
                    yield Finding(
                        relpath, node.lineno, self.id,
                        "direct MatrelConfig() construction in library "
                        "code — accept a config parameter and default "
                        "through default_config() so session/env "
                        "overrides flow")


class SpecKeyedCacheRule(Rule):
    """ML005: cache/memo dicts keyed by sharding-spec-ish values.

    ``PartitionSpec``/``NamedSharding``/``Mesh`` objects (and ``.spec``
    attributes) make treacherous dict keys: some are unhashable, others
    hash by identity across semantically-equal instances, and a jax
    upgrade can flip either property — turning a cache into a
    permanent miss (rebuild storm) or, worse, an identity-aliased hit.
    Key caches by the STABLE tuple you derive from the spec (axis
    names, grid shape, padded dims), the way the autotune table and the
    plan cache do."""

    id = "ML005"
    _NAME_RE = re.compile(r"(cache|memo)", re.IGNORECASE)
    _SPEC_CTORS = ("PartitionSpec", "NamedSharding", "Mesh")
    _SPEC_ATTRS = ("spec", "sharding")

    def applies_to(self, relpath: str) -> bool:
        return relpath.startswith("matrel_tpu/")

    def _cacheish(self, target: ast.AST) -> bool:
        if isinstance(target, ast.Name):
            return bool(self._NAME_RE.search(target.id))
        if isinstance(target, ast.Attribute):
            return bool(self._NAME_RE.search(target.attr))
        return False

    def _specish(self, key: ast.AST) -> bool:
        for node in ast.walk(key):
            if (isinstance(node, ast.Attribute)
                    and node.attr in self._SPEC_ATTRS):
                return True
            if isinstance(node, ast.Call):
                tail = _call_name(node.func).rsplit(".", 1)[-1]
                if tail in self._SPEC_CTORS:
                    return True
        return False

    def check(self, tree, relpath):
        for node in ast.walk(tree):
            key = None
            target = None
            if isinstance(node, ast.Subscript):
                target, key = node.value, node.slice
            elif isinstance(node, ast.Call):
                tail = _call_name(node.func).rsplit(".", 1)[-1]
                if tail in ("get", "setdefault") and node.args and \
                        isinstance(node.func, ast.Attribute):
                    target, key = node.func.value, node.args[0]
            if key is None or not self._cacheish(target):
                continue
            if self._specish(key):
                yield Finding(
                    relpath, node.lineno, self.id,
                    "cache keyed by a sharding spec / mesh object — "
                    "hashability is jax-version-dependent; key by the "
                    "derived stable tuple instead")


class RawTimingRule(Rule):
    """ML006: raw ``time.perf_counter()``/``time.time()``/
    ``time.monotonic()`` calls in library modules outside
    ``matrel_tpu/obs/``.

    Timing that matters belongs in the observability layer: a span
    (``obs.trace.span``/``phase``), so the
    measurement lands in the event log where ``history``, the chrome
    exporter and the drift auditor can read it — a bare perf_counter
    pair produces a number that dies in a local variable (or worse, a
    print). The round-9 conversion moved every hot-path timing onto
    spans; this rule keeps new code from regressing to private
    stopwatches. ``parallel/autotune.py`` is scoped out wholesale —
    it is the measurement subsystem, its wall-clocks ARE its output
    and persist to the autotune table (the ML001 precedent: scope
    encodes where the hazard is contextual). The two remaining
    legitimate exceptions (the analyze-mode op_hook, the serve
    queue-wait timestamps — both of which land their numbers in the
    event log) carry inline suppressions with their justification."""

    id = "ML006"
    _DOTTED = ("time.perf_counter", "time.time", "time.monotonic")
    _BARE = ("perf_counter", "monotonic")

    def applies_to(self, relpath: str) -> bool:
        # resilience/retry.py is scoped out like autotune: deadline /
        # backoff arithmetic IS that module's function (every other
        # resilience module stays in scope), and its outcomes land in
        # the event log as retry/degrade records
        return (relpath.startswith("matrel_tpu/")
                and not relpath.startswith("matrel_tpu/obs/")
                and relpath not in ("matrel_tpu/parallel/autotune.py",
                                    "matrel_tpu/resilience/retry.py"))

    def check(self, tree, relpath):
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            name = _call_name(node.func)
            if name in self._DOTTED or name in self._BARE:
                yield Finding(
                    relpath, node.lineno, self.id,
                    f"raw `{name}()` timing in library code — route "
                    "through obs.trace.span()/phase() so the "
                    "measurement lands in the event log")


class BroadSwallowRule(Rule):
    """ML007: bare/broad ``except`` that silently swallows and
    continues in library modules.

    ``except Exception: pass`` (or a bare ``except:``/``continue``
    body) erases the failure AND the information needed to classify it
    — exactly the anti-pattern the resilience layer's typed taxonomy
    (matrel_tpu/resilience/errors.py) exists to replace: a swallowed
    transient is a lost retry, a swallowed deterministic error is a
    silent wrong answer waiting to recur. Library code must either
    raise a TYPED error, classify-and-handle, or at minimum log the
    failure it chose to survive. The handful of legitimate
    swallow-and-continue sites (never-fail observability sinks, the
    autotune loop dropping strategies that fail to compile, fallback
    encoders) carry inline suppressions with their justification —
    deliberate, reviewable exceptions, not defaults. Narrow excepts
    (``except OSError:``) are out of scope: naming the exception IS
    the classification."""

    id = "ML007"
    _BROAD_NAMES = ("Exception", "BaseException")

    def applies_to(self, relpath: str) -> bool:
        return relpath.startswith("matrel_tpu/")

    def _broad(self, etype) -> bool:
        if etype is None:                       # bare except:
            return True
        if isinstance(etype, ast.Name):
            return etype.id in self._BROAD_NAMES
        if isinstance(etype, ast.Attribute):    # e.g. builtins.Exception
            return etype.attr in self._BROAD_NAMES
        return False

    @staticmethod
    def _swallows(body) -> bool:
        """True when the handler body ONLY discards: pass/continue
        statements (an ``...`` Ellipsis expression counts as pass)."""
        for stmt in body:
            if isinstance(stmt, (ast.Pass, ast.Continue)):
                continue
            if (isinstance(stmt, ast.Expr)
                    and isinstance(stmt.value, ast.Constant)
                    and stmt.value.value is Ellipsis):
                continue
            return False
        return True

    def check(self, tree, relpath):
        for node in ast.walk(tree):
            if not isinstance(node, ast.ExceptHandler):
                continue
            if self._broad(node.type) and self._swallows(node.body):
                yield Finding(
                    relpath, node.lineno, self.id,
                    "broad except swallows the failure and continues "
                    "— raise a typed error (resilience/errors.py), "
                    "classify-and-handle, or log what you chose to "
                    "survive")


class DevicePutRule(Rule):
    """ML008: ``jax.device_put`` in lowering modules — a layout change
    the planner cannot see or price.

    The reshard planner (matrel_tpu/parallel/reshard.py, round 10)
    exists so that every layout change lowers through a COSTED,
    peak-bounded step sequence: a raw ``device_put`` in a lowering
    module re-lays an array with whatever one-shot collective XLA
    picks, invisible to the byte model, to MV109's peak proof and to
    the obs decision records. Route layout changes through the planner
    (sharding constraints the reshard plan stages) instead. Out of
    scope by design: ``core/`` (construction-time initial placement is
    where arrays are BORN), the reshard module itself (it IS the
    sanctioned lowering), and ``utils/``/``obs/`` (checkpoint IO,
    host-side tooling). Two in-scope idioms are exempt: placements
    under ``jax.ensure_compile_time_eval()`` (host-built static
    metadata, the ML001-sanctioned pattern) and placements onto a
    fully-REPLICATED sharding (a ``rep``/``repl`` destination or
    ``replicated(...)`` call — metadata broadcast, not a re-lay).
    The remaining legit sites (host-built kernel tables placed onto
    their sharded layout at plan-build time) carry justified inline
    suppressions."""

    id = "ML008"
    _SCOPE = re.compile(
        r"^matrel_tpu/(executor\.py|session\.py|ops/|relational\.?/|"
        r"serve/|workloads/|ir/|parallel/)")
    _EXEMPT = ("matrel_tpu/parallel/reshard.py",)

    def applies_to(self, relpath: str) -> bool:
        return bool(self._SCOPE.match(relpath)) \
            and relpath not in self._EXEMPT

    @staticmethod
    def _replicated_dest(node: ast.Call) -> bool:
        dest = None
        if len(node.args) >= 2:
            dest = node.args[1]
        for kw in node.keywords:
            if kw.arg == "device":
                dest = kw.value
        if dest is None:
            return False
        if isinstance(dest, ast.Name) and re.match(r"^repl?\b", dest.id):
            return True
        if isinstance(dest, ast.Call):
            tail = _call_name(dest.func).rsplit(".", 1)[-1]
            if tail == "replicated":
                return True
        return False

    def check(self, tree, relpath):
        # (node, under ensure_compile_time_eval) — the ML001 walker
        stack: List[tuple] = [(tree, False)]
        while stack:
            node, under_cte = stack.pop()
            if isinstance(node, ast.With):
                for item in node.items:
                    name = _call_name(item.context_expr.func) if \
                        isinstance(item.context_expr, ast.Call) else ""
                    if name.endswith("ensure_compile_time_eval"):
                        under_cte = True
            if isinstance(node, ast.Call):
                tail = _call_name(node.func).rsplit(".", 1)[-1]
                if (tail == "device_put" and not under_cte
                        and not self._replicated_dest(node)):
                    yield Finding(
                        relpath, node.lineno, self.id,
                        "jax.device_put in a lowering module — a "
                        "layout change the planner cannot price; "
                        "route it through the reshard planner "
                        "(parallel/reshard.py) or a costed sharding "
                        "constraint")
            for child in ast.iter_child_nodes(node):
                stack.append((child, under_cte))


class KernelSeamRule(Rule):
    """ML009: Pallas kernel construction outside the kernel registry,
    in modules reachable from executor dispatch — the "one seam" rule.

    The sparse kernel registry (matrel_tpu/ops/kernel_registry.py)
    exists so that every kernel the executor's sparse-matmul dispatch
    can reach is REGISTERED: declared structure classes for the
    planner's stamp, admissibility MV110 can verify, a row the
    autotuner can measure, a forcing knob the degradation ladder can
    escape. A ``pallas_call`` authored elsewhere in ``ops/`` is a
    kernel the registry cannot select, verify, measure or escape —
    exactly the hardcoded branch the registry replaced (and the seam
    where future GPU/multi-backend kernels must land, ROADMAP north
    star). Scope: ``matrel_tpu/ops/`` (the executor's kernel modules);
    the registry module itself is the sanctioned home. The legacy
    SpMV/SpMM paths (ops/pallas_spmv.py, ops/pallas_spmm.py) predate
    the registry and stay unported this round — they carry justified inline suppressions, which double as
    the porting worklist."""

    id = "ML009"
    _SCOPE = re.compile(r"^matrel_tpu/ops/")
    _EXEMPT = ("matrel_tpu/ops/kernel_registry.py",)

    def applies_to(self, relpath: str) -> bool:
        return bool(self._SCOPE.match(relpath)) \
            and relpath not in self._EXEMPT

    def check(self, tree, relpath):
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            tail = _call_name(node.func).rsplit(".", 1)[-1]
            if tail == "pallas_call":
                yield Finding(
                    relpath, node.lineno, self.id,
                    "pallas_call outside the kernel registry — a "
                    "kernel the registry cannot select/verify/"
                    "measure/escape; define it in "
                    "ops/kernel_registry.py (the one seam) and "
                    "register it")


class JitSeamRule(Rule):
    """ML010: ``jax.jit`` call sites in ``matrel_tpu/`` outside the
    executor's region-emission seam (``executor.py``) and ``utils/``.

    The whole-plan fusion work (ir/fusion.py, docs/FUSION.md) made
    program emission a PLANNER decision: the executor compiles whole
    plans, fused regions and per-op staged units through ONE seam,
    where the boundary is stamped, measured (the autotune ``fuse|``
    family), verified (MV111) and escapable (degradation rung 3). A
    ``jax.jit`` authored elsewhere in the package is a compiled
    program the planner cannot see, the dispatch-count accounting
    cannot count, and the fused-vs-staged measurement cannot sweep —
    the ML009 "one seam" argument applied to programs instead of
    kernels. Scope: the package minus executor.py (the seam) and
    utils/ (host-side tooling helpers); harness scripts
    (bench/tools/tests) are out of scope — they ARE measurement.
    The pre-existing legitimate sites (workload runner caches, ops
    table builders, autotune probes, core constructors) carry
    justified inline suppressions, which double as the worklist for
    porting them onto the seam."""

    id = "ML010"
    _EXEMPT = ("matrel_tpu/executor.py",)

    def applies_to(self, relpath: str) -> bool:
        return (relpath.startswith("matrel_tpu/")
                and relpath not in self._EXEMPT
                and not relpath.startswith("matrel_tpu/utils/"))

    @staticmethod
    def _is_jit(node: ast.AST) -> bool:
        # Name/Attribute targets only: an ast.Call target (the
        # `jax.jit(f)(x)` outer call's func) must NOT match, or an
        # immediately-invoked jit site reports twice at one line
        if isinstance(node, ast.Call):
            return False
        return _call_name(node).rsplit(".", 1)[-1] == "jit"

    def check(self, tree, relpath):
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) and self._is_jit(node.func):
                yield Finding(
                    relpath, node.lineno, self.id,
                    "jax.jit outside the executor's region-emission "
                    "seam — a compiled program the planner cannot "
                    "see/measure/escape; emit it through "
                    "matrel_tpu/executor.py (or justify with an "
                    "inline suppression)")
            elif isinstance(node, (ast.FunctionDef,
                                   ast.AsyncFunctionDef)):
                # bare `@jax.jit` only — call-form decorators
                # (`@jax.jit` with args, `@partial(jax.jit, ...)`)
                # are ast.Calls the branch above already walks
                for dec in node.decorator_list:
                    if not isinstance(dec, ast.Call) \
                            and self._is_jit(dec):
                        yield Finding(
                            relpath, dec.lineno, self.id,
                            "@jax.jit outside the executor's "
                            "region-emission seam — a compiled "
                            "program the planner cannot see/measure/"
                            "escape; emit it through "
                            "matrel_tpu/executor.py (or justify with "
                            "an inline suppression)")


class UnboundedQueueRule(Rule):
    """ML011: unbounded-queue growth idioms in the serve plane.

    The overload control plane (docs/OVERLOAD.md) exists because an
    unbounded queue turns overload into memory exhaustion plus
    unbounded latency — the exact failure the typed AdmissionShed
    contract replaces with refusal. Two idioms are pinned:

    - ``deque()`` / ``queue.Queue()`` (or LifoQueue/PriorityQueue)
      constructed WITHOUT a bound (no maxlen/maxsize argument) inside
      ``matrel_tpu/serve/`` — the modules whose queues sit on the
      admission path. A queue that is bounded by surrounding shed
      logic rather than by its constructor carries a justified inline
      suppression (the AdmissionQueue's per-tenant deques: a maxlen
      deque DROPS silently, and refusal must be typed).
    - ``threading.Thread(...)`` without an explicit ``daemon=``
      anywhere in ``matrel_tpu/``: a non-daemon worker left running
      wedges interpreter shutdown — every sanctioned worker/helper
      thread in the package states its daemon-ness at the call site.
    """

    id = "ML011"
    _QUEUE_TAILS = ("Queue", "LifoQueue", "PriorityQueue")
    _BOUND_KW = ("maxlen", "maxsize")

    def applies_to(self, relpath: str) -> bool:
        return relpath.startswith("matrel_tpu/")

    @staticmethod
    def _has_bound(node: ast.Call, kw_names, bound_pos: int) -> bool:
        """An explicit bound: the named keyword, or enough positional
        args to reach the bound's slot — ``deque(iterable)`` is NOT
        bounded (the first positional is the iterable; maxlen is the
        second), while ``queue.Queue(n)``'s first positional IS
        maxsize."""
        if any(k.arg in kw_names for k in node.keywords):
            return True
        return len(node.args) >= bound_pos

    def check(self, tree, relpath):
        in_serve = relpath.startswith("matrel_tpu/serve/")
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            tail = _call_name(node.func).rsplit(".", 1)[-1]
            if in_serve and tail == "deque" \
                    and not self._has_bound(node, ("maxlen",), 2):
                yield Finding(
                    relpath, node.lineno, self.id,
                    "unbounded deque() on the serve path — bound it "
                    "(maxlen=) or shed typed past an explicit bound "
                    "(AdmissionShed), with a justified suppression "
                    "when the bound lives in surrounding logic")
            elif in_serve and tail in self._QUEUE_TAILS \
                    and not self._has_bound(node, ("maxsize",), 1):
                yield Finding(
                    relpath, node.lineno, self.id,
                    f"unbounded queue.{tail}() on the serve path — "
                    "pass maxsize (or shed typed past an explicit "
                    "bound)")
            elif tail == "Thread" and not any(
                    k.arg == "daemon" for k in node.keywords):
                yield Finding(
                    relpath, node.lineno, self.id,
                    "threading.Thread without an explicit daemon= — "
                    "a non-daemon worker wedges interpreter "
                    "shutdown; state the thread's lifecycle at the "
                    "call site")


@dataclasses.dataclass(frozen=True)
class ResultCacheSeamRule(Rule):
    """ML012: ResultCache entry payloads mutate ONLY through the
    sanctioned patch/apply seam in serve/result_cache.py.

    The IVM plane (serve/ivm.py; docs/IVM.md) made cached entries
    LONG-LIVED MUTABLE STATE: a patched entry's result/deps/bound
    must change together, under the cache lock, with the byte
    accounting and the provenance stamp kept coherent — so every
    mutation goes through ResultCache.apply_patch / rekey / drop /
    put (the ML009 one-kernel-seam and ML010 one-jit-seam idiom,
    applied to cached state). A module that pokes an entry's fields
    or the cache's internal stores directly produces answers whose
    provenance nobody can verify (MV113 would assert a bound the
    mutation silently voided) and byte accounting that drifts from
    the entries it claims to bound. Pinned, in matrel_tpu/ outside
    serve/result_cache.py:

    - attribute ASSIGNMENT (plain, augmented, or del) to a CacheEntry
      payload field — result, dep_ids, pins, nbytes, key_hash,
      err_bound, delta_gen, delta_rule, prec, ivm_id — on any object
      (``dataclasses.replace`` builds a NEW entry and is fine; the
      seam inserts it);
    - any use of an attribute named ``_entries`` / ``_stale`` (the
      cache's internal stores): subscript stores/deletes, mutating
      method calls (pop/popitem/clear/update/setdefault/move_to_end),
      or reads — outside the owning module even a read races the
      serve worker without the cache lock.
    """

    id = "ML012"
    _ENTRY_FIELDS = ("result", "dep_ids", "pins", "nbytes", "key_hash",
                     "err_bound", "delta_gen", "delta_rule", "prec",
                     "ivm_id")
    _STORES = ("_entries", "_stale")

    def applies_to(self, relpath: str) -> bool:
        return (relpath.startswith("matrel_tpu/")
                and relpath != "matrel_tpu/serve/result_cache.py")

    def check(self, tree, relpath):
        for node in ast.walk(tree):
            targets = []
            if isinstance(node, ast.Assign):
                targets = node.targets
            elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
                targets = [node.target]
            elif isinstance(node, ast.Delete):
                targets = node.targets
            for t in targets:
                if isinstance(t, ast.Attribute) \
                        and t.attr in self._ENTRY_FIELDS:
                    yield Finding(
                        relpath, node.lineno, self.id,
                        f"direct store to a cache-entry payload field "
                        f".{t.attr} — mutate entries only through the "
                        f"ResultCache patch/apply seam "
                        f"(apply_patch/rekey/drop/put in "
                        f"serve/result_cache.py)")
            if isinstance(node, ast.Attribute) \
                    and node.attr in self._STORES:
                yield Finding(
                    relpath, node.lineno, self.id,
                    f"direct access to the result cache's internal "
                    f".{node.attr} store — the entries mutate only "
                    f"under the cache lock through the sanctioned "
                    f"seam (serve/result_cache.py)")


class TimingAccumulationRule(Rule):
    """ML013: ad-hoc latency accumulation outside the metrics
    registry — ``.append()``/``.extend()`` onto a latency-named list
    in ``matrel_tpu/`` outside ``matrel_tpu/obs/``.

    The live telemetry plane (obs/metrics.py round 15) made quantiles
    a SHARED definition: every timing metric flows through the
    registry's sketch/histogram API (or ``obs.metrics.percentile``),
    so the live endpoint, ``history``'s replay and ``top`` can never
    disagree beyond the sketch's documented relative error — and
    memory stays bounded by construction. A private
    ``latencies.append(ms)`` list is the pre-sketch anti-pattern
    wearing new clothes: unbounded on a long-lived server, invisible
    to the endpoint, and quantiled by whatever ad-hoc rank math its
    author re-derives (the exact drift the history-vs-live fix
    removed). ML006 pins the CLOCK CALLS; this rule pins the
    ACCUMULATION — both ends of a private stopwatch.

    Scope: the package minus ``obs/`` (the registry and its readers
    ARE the sanctioned accumulation) ; harness scripts (bench/tools/
    tests) are out of scope — measurement is their output (the ML006
    autotune precedent). The two legitimate in-scope sites — the
    brownout controller's bounded sliding window (measurement IS that
    subsystem, and its p95 reads through the shared definition) and
    the serve worker's per-cycle overload-event assembly (the values
    land in the event log) — carry justified inline suppressions.

    Matched names: the append target's variable/attribute name (or a
    string subscript key) containing a latency-ish token — ``lat``/
    ``latency``/``latencies``, ``wait``/``waits``, ``duration(s)``,
    ``elapsed``, ``timing(s)`` — or ending in ``_ms``.
    """

    id = "ML013"
    _TIMING_RE = re.compile(
        r"(?i)(?:^|_)(lat|lats|latency|latencies|wait|waits|"
        r"dur|durs|duration|durations|elapsed|timing|timings)(?:$|_)"
        r"|_ms$")

    def applies_to(self, relpath: str) -> bool:
        return (relpath.startswith("matrel_tpu/")
                and not relpath.startswith("matrel_tpu/obs/"))

    @classmethod
    def _target_name(cls, node: ast.AST) -> str:
        """The accumulation target's human name: ``waits`` for
        ``waits.append``, ``_waits`` for ``self._waits.append``,
        ``latencies`` for ``row["latencies"].append``."""
        if isinstance(node, ast.Name):
            return node.id
        if isinstance(node, ast.Attribute):
            return node.attr
        if isinstance(node, ast.Subscript):
            sl = node.slice
            if isinstance(sl, ast.Constant) and isinstance(sl.value,
                                                           str):
                return sl.value
        return ""

    def check(self, tree, relpath):
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call) \
                    or not isinstance(node.func, ast.Attribute):
                continue
            if node.func.attr not in ("append", "extend"):
                continue
            name = self._target_name(node.func.value)
            if name and self._TIMING_RE.search(name):
                yield Finding(
                    relpath, node.lineno, self.id,
                    f"ad-hoc timing accumulation `{name}."
                    f"{node.func.attr}(...)` — record through the "
                    "metrics registry's sketch/histogram API "
                    "(obs/metrics.py) so live and offline quantiles "
                    "share one bounded-memory definition")


class FleetSeamRule(Rule):
    """ML014: cross-slice state mutation pinned onto the fleet API
    (serve/fleet.py; docs/FLEET.md).

    The fleet made OTHER sessions' result caches reachable: every
    slice owns one, and the directory/replication protocol depends on
    exactly one module mutating them — a serve/ module that writes
    another slice's cache directly produces entries the directory
    never recorded (unreachable by the hit-anywhere protocol, wrong
    ownership on failover) and bypasses the replication pricing that
    keeps migrations under the HBM budget. Pinned, in
    ``matrel_tpu/serve/`` outside ``fleet.py`` and the cache's own
    module: a call to a MUTATING ResultCache method (put / drop /
    apply_patch / rekey / invalidate_deps / clear / rebuild_stale)
    whose receiver chain reaches ``._result_cache`` through anything
    other than plain ``self`` / ``self.session`` — e.g.
    ``fleet.slices[i].session._result_cache.put(...)``. A session
    mutating ITS OWN cache (the IVM plane, the rebind path) is the
    sanctioned single-slice seam and stays clean."""

    id = "ML014"
    _MUT = ("put", "drop", "apply_patch", "rekey", "invalidate_deps",
            "clear", "rebuild_stale")

    def applies_to(self, relpath: str) -> bool:
        return (relpath.startswith("matrel_tpu/serve/")
                and relpath not in ("matrel_tpu/serve/fleet.py",
                                    "matrel_tpu/serve/result_cache.py"))

    def check(self, tree, relpath):
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            if not isinstance(f, ast.Attribute) \
                    or f.attr not in self._MUT:
                continue
            chain = []
            cur = f.value
            through_subscript = False
            while True:
                if isinstance(cur, ast.Attribute):
                    chain.append(cur.attr)
                    cur = cur.value
                elif isinstance(cur, ast.Subscript):
                    through_subscript = True
                    cur = cur.value
                elif isinstance(cur, ast.Call):
                    cur = cur.func
                else:
                    break
            if "_result_cache" not in chain:
                continue
            # sanctioned receivers: a session mutating its OWN cache
            # — self._result_cache / self.session._result_cache / the
            # conventional sess/session local alias. Anything reached
            # through a subscript (slices[i]) or a foreign object is
            # another slice's state.
            own_root = (isinstance(cur, ast.Name)
                        and cur.id in ("self", "sess", "session"))
            sanctioned = (own_root and not through_subscript
                          and set(chain) <= {"_result_cache",
                                             "session"})
            if not sanctioned:
                yield Finding(
                    relpath, node.lineno, self.id,
                    f"cross-slice result-cache mutation "
                    f"`...{'.'.join(reversed(chain))}.{f.attr}(...)`"
                    f" outside the fleet API — another slice's cache "
                    f"mutates only through serve/fleet.py (the "
                    f"directory/replication seam, docs/FLEET.md)")


class ProvenanceSeamRule(Rule):
    """ML015: answer-lineage stamps are written ONLY by the ledger's
    sanctioned writers in obs/provenance.py (the ML012/ML014 one-seam
    idiom applied to lineage).

    The answer provenance ledger (docs/OBSERVABILITY.md tier 4) makes
    ``CacheEntry.provenance`` and the substitution leaf's
    ``attrs["provenance"]`` the account of where a served answer came
    from — and MV115 cross-checks that account against the mechanism
    stamps, while ``why --audit`` replays answers against the bounds
    it records. Both are only sound if the stamps have exactly one
    producer: a module hand-writing a provenance dict produces
    lineage the ledger never witnessed (un-audited, un-renderable,
    schema-drifting) — precisely the unverifiable-answer class ML012
    pins for cache payloads. Serve/session modules CALL
    ``stamp_entry`` / ``stamp_patched`` / ``stamp_leaf``; they never
    build the stamp themselves. Pinned, in ``matrel_tpu/`` outside
    ``matrel_tpu/obs/provenance.py``:

    - attribute assignment (plain, augmented, annotated, or del) to a
      ``.provenance`` field on any object;
    - a subscript store ``X["provenance"] = ...`` (the attrs-dict
      route around the attribute check);
    - a ``provenance=`` keyword in a ``with_attrs(...)`` call (the
      immutable-expr route).

    Reads are fine everywhere — the ledger exists to be read.
    """

    id = "ML015"

    def applies_to(self, relpath: str) -> bool:
        return (relpath.startswith("matrel_tpu/")
                and relpath != "matrel_tpu/obs/provenance.py")

    def check(self, tree, relpath):
        for node in ast.walk(tree):
            targets = []
            if isinstance(node, ast.Assign):
                targets = node.targets
            elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
                targets = [node.target]
            elif isinstance(node, ast.Delete):
                targets = node.targets
            for t in targets:
                if isinstance(t, ast.Attribute) \
                        and t.attr == "provenance":
                    yield Finding(
                        relpath, node.lineno, self.id,
                        "direct store to a .provenance stamp — "
                        "lineage is written only by the ledger's "
                        "stamp_entry/stamp_patched/stamp_leaf "
                        "(obs/provenance.py), so MV115 and the "
                        "audit replay can trust it")
                if isinstance(t, ast.Subscript) \
                        and isinstance(t.slice, ast.Constant) \
                        and t.slice.value == "provenance":
                    yield Finding(
                        relpath, node.lineno, self.id,
                        "subscript store to a ['provenance'] stamp — "
                        "lineage is written only by the ledger's "
                        "stamp writers (obs/provenance.py)")
            if isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "with_attrs":
                for kw in node.keywords:
                    if kw.arg == "provenance":
                        yield Finding(
                            relpath, node.lineno, self.id,
                            "with_attrs(provenance=...) outside the "
                            "ledger — thread lineage onto leaves via "
                            "stamp_leaf (obs/provenance.py)")


class TemplateKeyRule(Rule):
    """ML016: plan-template / CSE caches keyed by identity or spec
    values instead of the canonical structural key (ML005 extended to
    the multi-query-optimization plane, serve/mqo.py).

    A template entry outlives the queries that built it — that is the
    point — so its key must mean the same thing at probe time as it
    did at insert time. ``id()`` is recycled the moment the original
    object dies (a false hit rebinds a STRANGER's matrices into a
    compiled plan); node ``.uid`` values are per-tree counters that
    collide across independently-built expressions; spec/sharding
    objects hash by identity or not at all (the ML005 hazard). The
    only sound key is the leaf-abstracted STRUCTURAL key
    (``mqo.template_key`` / ``session._plan_key``) — derived strings
    whose equality IS plan equivalence. Pinned: subscript stores and
    ``get``/``setdefault`` consults on template-/hoist-named dicts
    whose key expression reaches an ``id(...)`` call or a
    ``.uid``/``.spec``/``.sharding`` attribute. Local first-occurrence
    maps (``classes.setdefault(id(m), ...)`` inside one
    ``template_key`` walk) are fine — they die with the walk, which
    is why the rule scopes by cache NAME, not by module."""

    id = "ML016"
    _NAME_RE = re.compile(r"(template|tpl|hoist)", re.IGNORECASE)
    _UNSTABLE_ATTRS = ("uid", "spec", "sharding")

    def applies_to(self, relpath: str) -> bool:
        return relpath.startswith("matrel_tpu/")

    def _cacheish(self, target: ast.AST) -> bool:
        if isinstance(target, ast.Name):
            return bool(self._NAME_RE.search(target.id))
        if isinstance(target, ast.Attribute):
            return bool(self._NAME_RE.search(target.attr))
        return False

    def _unstable(self, key: ast.AST) -> Optional[str]:
        for node in ast.walk(key):
            if isinstance(node, ast.Call) \
                    and _call_name(node.func).rsplit(".", 1)[-1] == "id":
                return "id()"
            if isinstance(node, ast.Attribute) \
                    and node.attr in self._UNSTABLE_ATTRS:
                return f".{node.attr}"
        return None

    def check(self, tree, relpath):
        for node in ast.walk(tree):
            key = None
            target = None
            if isinstance(node, ast.Subscript):
                target, key = node.value, node.slice
            elif isinstance(node, ast.Call):
                tail = _call_name(node.func).rsplit(".", 1)[-1]
                if tail in ("get", "setdefault") and node.args and \
                        isinstance(node.func, ast.Attribute):
                    target, key = node.func.value, node.args[0]
            if key is None or not self._cacheish(target):
                continue
            bad = self._unstable(key)
            if bad is not None:
                yield Finding(
                    relpath, node.lineno, self.id,
                    f"template/CSE cache keyed by {bad} — identity "
                    f"and spec values do not survive the entry (a "
                    f"recycled id() falsely rebinds, uids collide "
                    f"across trees); key by the canonical structural "
                    f"key (mqo.template_key / session._plan_key)")


class LockSeamRule(Rule):
    """ML017: bare ``threading.Lock()``/``RLock()`` construction in
    ``matrel_tpu/`` outside the ``utils/lockdep.py`` seam.

    The concurrency sanitizer (docs/CONCURRENCY.md) hangs off ONE
    construction seam: ``lockdep.make_lock(name)`` /
    ``make_rlock(name)`` return raw threading primitives by default
    (zero objects — the structural-zero contract) and instrumented
    wrappers under ``config.lockdep_enable``. A lock built bare is
    invisible to all three layers the seam feeds: it has no inventory
    name (docs/CONCURRENCY.md's lock table and lockcheck's LK1xx
    findings key on them), the runtime order graph never sees its
    acquisitions, and the race drill cannot prove schedules over it —
    the ML009/ML010 one-seam argument applied to locks.
    ``Condition``/``Event``/``Semaphore`` stay legal: they are
    signalling primitives, not mutual-exclusion state, and the
    Conditions in the serve plane deliberately WRAP a seam-built lock.
    The sanitizer's own internal guard in utils/lockdep.py is the one
    necessarily-raw lock (it cannot instrument itself)."""

    id = "ML017"
    _SEAM = ("matrel_tpu/utils/lockdep.py",)

    def applies_to(self, relpath: str) -> bool:
        return (relpath.startswith("matrel_tpu/")
                and relpath not in self._SEAM)

    def check(self, tree, relpath):
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            name = _call_name(node.func)
            if name in ("threading.Lock", "threading.RLock",
                        "Lock", "RLock"):
                kind = name.rsplit(".", 1)[-1]
                yield Finding(
                    relpath, node.lineno, self.id,
                    f"bare threading.{kind}() outside the lockdep "
                    f"seam — construct it via lockdep.make_"
                    f"{'r' if kind == 'RLock' else ''}lock"
                    f"(\"<inventory.name>\") (utils/lockdep.py) so "
                    f"it is named, order-tracked and drill-able")


class CoeffSeamRule(Rule):
    """ML018: raw ``drift.load_table`` consult in planner/serve code
    outside the ``parallel/coeffs.py`` seam.

    The cost-model loop (docs/COST_MODEL.md) hangs off ONE coefficient
    reader: ``parallel/coeffs.py`` parses the drift table once per
    file state (stat-signature memoized), drops non-finite rows, and
    stamps the coefficient EPOCH the session embeds in every plan key
    (``coeffv:``). A planner or serve module that calls
    ``drift.load_table`` directly re-reads and re-parses the raw JSON
    on its own schedule: it can rank by a table state no other
    consumer saw, its decisions carry no epoch (so a re-plan round
    cannot invalidate the plans it influenced), and the NaN/zero-ms
    hardening lives only in the seam — the ML009/ML010 one-seam
    argument applied to learned coefficients. ``obs/`` is out of
    scope (the auditor/controller own the table and its writers);
    the seam itself is exempt."""

    id = "ML018"
    _EXEMPT = ("matrel_tpu/parallel/coeffs.py",)

    def applies_to(self, relpath: str) -> bool:
        return (relpath.startswith("matrel_tpu/")
                and not relpath.startswith("matrel_tpu/obs/")
                and relpath not in self._EXEMPT)

    def check(self, tree, relpath):
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom):
                if (node.module or "").endswith("obs.drift") and any(
                        a.name == "load_table" for a in node.names):
                    yield Finding(
                        relpath, node.lineno, self.id,
                        "load_table imported from obs.drift outside "
                        "the coefficient seam — consult "
                        "parallel/coeffs.py (strategy_row/"
                        "class_coefficients/epoch) so the read is "
                        "memoized, hardened and epoch-stamped")
            elif isinstance(node, ast.Call):
                # drift-qualified calls only (drift.load_table,
                # drift_lib.load_table): the autotune table has its
                # own same-named reader in parallel/autotune.py and
                # is a different store with its own seam
                name = _call_name(node.func)
                if (name.rsplit(".", 1)[-1] == "load_table"
                        and "drift" in name.rsplit(".", 1)[0]):
                    yield Finding(
                        relpath, node.lineno, self.id,
                        "raw drift.load_table consult outside the "
                        "coefficient seam — consult "
                        "parallel/coeffs.py (strategy_row/"
                        "class_coefficients/epoch) so the read is "
                        "memoized, hardened and epoch-stamped")


class DurableIoSeamRule(Rule):
    """ML019: raw file IO in ``matrel_tpu/serve/`` outside the
    spill/checkpoint seam.

    The durability plane (docs/DURABILITY.md) hangs off ONE writer:
    ``serve/spill.py`` stages every artifact through the checkpoint
    format's atomic tmp+rename with a streamed sha1, and its restore
    path treats any mismatch as a typed miss (SnapshotCorruption —
    recompute, never a wrong answer). A serve module that opens files
    on its own creates durable state save_state() does not know to
    freeze and restore() cannot verify — a restart either loses it
    silently or thaws bytes nothing checksummed. The ML009/ML010
    one-seam idiom applied to durable serving state; the seam itself
    is exempt, and modules outside serve/ (obs exporters, the
    checkpoint manager, tools) keep their own IO discipline."""

    id = "ML019"
    _EXEMPT = ("matrel_tpu/serve/spill.py",)
    #: call tokens whose tail identifies a raw durable-IO primitive
    _IO_TAILS = {"save": ("np", "numpy"), "load": ("np", "numpy"),
                 "dump": ("json",), "dumps": (),
                 "replace": ("os",), "remove": ("os",),
                 "unlink": ("os",)}

    def applies_to(self, relpath: str) -> bool:
        return (relpath.startswith("matrel_tpu/serve/")
                and relpath not in self._EXEMPT)

    def check(self, tree, relpath):
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            name = _call_name(node.func)
            head, _, tail = name.rpartition(".")
            if name == "open":
                yield Finding(
                    relpath, node.lineno, self.id,
                    "raw open() in serve code — durable serving "
                    "state goes through the spill/checkpoint seam "
                    "(serve/spill.py) so artifacts are sha1-stamped, "
                    "atomically renamed and restore-verifiable")
            elif tail in ("save", "load", "dump", "replace",
                          "remove", "unlink"):
                owners = self._IO_TAILS.get(tail, ())
                if head in owners:
                    yield Finding(
                        relpath, node.lineno, self.id,
                        f"raw {name}() in serve code — durable "
                        "serving state goes through the spill/"
                        "checkpoint seam (serve/spill.py) so "
                        "artifacts are sha1-stamped, atomically "
                        "renamed and restore-verifiable")


RULES: Sequence[Rule] = (HostSyncRule(), NoDensifyRule(),
                        ShardMapOutSpecsRule(), ConfigFlowRule(),
                        SpecKeyedCacheRule(), RawTimingRule(),
                        BroadSwallowRule(), DevicePutRule(),
                        KernelSeamRule(), JitSeamRule(),
                        UnboundedQueueRule(), ResultCacheSeamRule(),
                        TimingAccumulationRule(), FleetSeamRule(),
                        ProvenanceSeamRule(), TemplateKeyRule(),
                        LockSeamRule(), CoeffSeamRule(),
                        DurableIoSeamRule())


def _suppressed_codes(line: str) -> set:
    """Codes disabled on this line. Tokens after the code list are
    justification prose (mandatory by convention, ignored by the
    parser): ``# matlint: disable=ML001 analyze-mode op_hook``."""
    m = _SUPPRESS_RE.search(line)
    if not m:
        return set()
    return {tok for tok in re.split(r"[\s,]+", m.group(1))
            if re.fullmatch(r"ML\d+", tok)}


def lint_file(path: str, rules: Sequence[Rule] = RULES,
              relpath: Optional[str] = None) -> List[Finding]:
    """All unsuppressed findings for one file. ``relpath`` overrides
    the repo-relative path used for rule scoping (fixture tests lint
    temp files AS IF they lived at a package path)."""
    rel = relpath if relpath is not None else _rel(path)
    with open(path, encoding="utf-8") as f:
        src = f.read()
    try:
        tree = ast.parse(src, filename=path)
    except SyntaxError as e:
        return [Finding(rel, e.lineno or 0, "ML000",
                        f"file does not parse: {e.msg}")]
    lines = src.splitlines()
    out: List[Finding] = []
    for rule in rules:
        if not rule.applies_to(rel):
            continue
        for f in rule.check(tree, rel):
            line = lines[f.line - 1] if 0 < f.line <= len(lines) else ""
            if f.rule in _suppressed_codes(line):
                continue
            out.append(f)
    return sorted(out, key=lambda f: (f.path, f.line, f.rule))


def iter_python_files(paths: Sequence[str]) -> Iterator[str]:
    for p in paths:
        full = p if os.path.isabs(p) else os.path.join(REPO, p)
        if os.path.isfile(full):
            yield full
        elif os.path.isdir(full):
            for dirpath, dirnames, filenames in os.walk(full):
                dirnames[:] = [d for d in dirnames
                               if d != "__pycache__"]
                for fn in sorted(filenames):
                    if fn.endswith(".py"):
                        yield os.path.join(dirpath, fn)


def lint_paths(paths: Sequence[str] = DEFAULT_PATHS) -> List[Finding]:
    out: List[Finding] = []
    for f in iter_python_files(paths):
        out.extend(lint_file(f))
    return out


def main(argv: Sequence[str]) -> int:
    if "--list-rules" in argv:
        for r in RULES:
            doc = (r.__doc__ or "").strip().splitlines()[0]
            print(f"{r.id}  {doc}")
        return 0
    paths = [a for a in argv if not a.startswith("-")] or list(
        DEFAULT_PATHS)
    findings = lint_paths(paths)
    for f in findings:
        print(f.render())
    n = len(findings)
    print(f"matlint: {n} finding(s) in scan set {tuple(paths)}")
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
