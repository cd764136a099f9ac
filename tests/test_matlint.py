"""matlint (tools/matlint.py): fixture-based proof that every rule
fires on its hazard, that the inline suppression syntax silences it,
and that the repo itself lints clean — the tier-1 enforcement of
`make lint`'s first half (tests cannot silently skip what they
themselves run)."""

import textwrap

import pytest

from tools import matlint


def _lint(tmp_path, source, relpath):
    f = tmp_path / "fixture.py"
    f.write_text(textwrap.dedent(source))
    return matlint.lint_file(str(f), relpath=relpath)


def _rules(findings):
    return sorted({f.rule for f in findings})


class TestML001HostSync:
    def test_fires_on_block_until_ready(self, tmp_path):
        src = """
            import jax
            def lower(x):
                out = x + 1
                jax.block_until_ready(out)
                return out
        """
        got = _lint(tmp_path, src, "matrel_tpu/ops/custom.py")
        assert _rules(got) == ["ML001"]

    def test_fires_on_method_attribute_form(self, tmp_path):
        src = """
            def lower(x):
                x.block_until_ready()
                return x
        """
        got = _lint(tmp_path, src, "matrel_tpu/executor.py")
        assert _rules(got) == ["ML001"]

    def test_asarray_in_lowerer_method(self, tmp_path):
        src = """
            import numpy as np
            class MyLowerer:
                def _eval(self, x):
                    return np.asarray(x)
        """
        got = _lint(tmp_path, src, "matrel_tpu/executor.py")
        assert _rules(got) == ["ML001"]

    def test_asarray_sanctioned_under_compile_time_eval(self, tmp_path):
        src = """
            import jax
            import numpy as np
            class MyLowerer:
                def _eval(self, m):
                    with jax.ensure_compile_time_eval():
                        return np.asarray(m.rows)
        """
        assert _lint(tmp_path, src, "matrel_tpu/executor.py") == []

    def test_out_of_scope_module_ignored(self, tmp_path):
        src = """
            import jax
            def wait(x):
                jax.block_until_ready(x)
        """
        # obs/ and utils/ legitimately sync (analyze mode, checkpoint)
        assert _lint(tmp_path, src, "matrel_tpu/obs/analyze.py") == []


class TestML002NoDensify:
    def test_fires_in_ops_module(self, tmp_path):
        src = """
            def apply(S, x):
                return S.to_dense() @ x
        """
        got = _lint(tmp_path, src, "matrel_tpu/ops/spgemm.py")
        assert _rules(got) == ["ML002"]

    def test_todense_variant(self, tmp_path):
        src = """
            def apply(S):
                return S.todense()
        """
        got = _lint(tmp_path, src, "matrel_tpu/ops/spmm.py")
        assert _rules(got) == ["ML002"]

    def test_executor_dispatch_is_allowed(self, tmp_path):
        # the densify FALLBACK lives in the executor where the planner
        # prices it — only ops/ kernels are no-densify territory
        src = """
            def fallback(node, cfg):
                return node.attrs["matrix"].to_dense(cfg).data
        """
        assert _lint(tmp_path, src, "matrel_tpu/executor.py") == []


class TestML003ShardMapOutSpecs:
    def test_fires_without_out_specs(self, tmp_path):
        src = """
            from matrel_tpu.utils.compat import shard_map
            def f(kernel, mesh, specs):
                return shard_map(kernel, mesh=mesh, in_specs=specs)
        """
        got = _lint(tmp_path, src, "matrel_tpu/ops/new_kernel.py")
        assert _rules(got) == ["ML003"]

    def test_keyword_out_specs_clean(self, tmp_path):
        src = """
            from matrel_tpu.utils.compat import shard_map
            def f(kernel, mesh, specs, P):
                return shard_map(kernel, mesh=mesh, in_specs=specs,
                                 out_specs=P())
        """
        assert _lint(tmp_path, src, "matrel_tpu/ops/new_kernel.py") == []

    def test_positional_form_clean(self, tmp_path):
        src = """
            def f(sm, kernel, mesh, ins, outs):
                return sm.shard_map(kernel, mesh, ins, outs)
        """
        assert _lint(tmp_path, src, "matrel_tpu/ops/new_kernel.py") == []


class TestML004ConfigFlow:
    def test_fires_in_package(self, tmp_path):
        src = """
            from matrel_tpu.config import MatrelConfig
            def plan(node):
                cfg = MatrelConfig()
                return cfg.block_size
        """
        got = _lint(tmp_path, src, "matrel_tpu/parallel/newpass.py")
        assert _rules(got) == ["ML004"]

    def test_harness_scripts_exempt(self, tmp_path):
        src = """
            from matrel_tpu.config import MatrelConfig
            cfg = MatrelConfig(obs_level="off")
        """
        assert _lint(tmp_path, src, "tools/new_probe.py") == []
        assert _lint(tmp_path, src, "chip_smoke.py") == []

    def test_config_module_itself_exempt(self, tmp_path):
        src = """
            class MatrelConfig:
                pass
            _default = MatrelConfig()
        """
        assert _lint(tmp_path, src, "matrel_tpu/config.py") == []


class TestML005SpecKeyedCache:
    def test_fires_on_spec_keyed_store(self, tmp_path):
        src = """
            _cache = {}
            def put(m, v):
                _cache[m.spec] = v
        """
        got = _lint(tmp_path, src, "matrel_tpu/core/newcache.py")
        assert _rules(got) == ["ML005"]

    def test_fires_on_sharding_ctor_get(self, tmp_path):
        src = """
            from jax.sharding import NamedSharding
            def lookup(memo_tbl, mesh, spec):
                return memo_tbl.get(NamedSharding(mesh, spec))
        """
        got = _lint(tmp_path, src, "matrel_tpu/core/newcache.py")
        assert _rules(got) == ["ML005"]

    def test_stable_tuple_keys_clean(self, tmp_path):
        src = """
            _cache = {}
            def put(n, k, gx, gy, v):
                _cache[(n, k, gx, gy)] = v
        """
        assert _lint(tmp_path, src, "matrel_tpu/core/newcache.py") == []


class TestML005ResultCacheKeying:
    """The serve/ result cache's keying contract (ISSUE 5): entries
    key by the canonical STRUCTURAL plan key. A spec- or sharding-
    keyed variant is exactly the ML005 hazard — the fixture proves the
    rule would catch that regression, and the real module must scan
    clean."""

    def test_spec_keyed_result_cache_fixture_fires(self, tmp_path):
        src = """
            class ResultCache:
                def __init__(self):
                    self._entry_cache = {}
                def put(self, out, v):
                    self._entry_cache[out.sharding] = v
        """
        got = _lint(tmp_path, src,
                    "matrel_tpu/serve/result_cache.py")
        assert _rules(got) == ["ML005"]

    def test_real_result_cache_is_ml005_clean(self):
        import os
        got = matlint.lint_file(
            os.path.join(matlint.REPO, "matrel_tpu", "serve",
                         "result_cache.py"))
        assert [f for f in got if f.rule == "ML005"] == []


class TestML006RawTiming:
    """Raw wall-clock timing in library modules (ISSUE 6): timing
    belongs in spans so the measurement lands in the event
    log where history / the chrome exporter / the drift auditor can
    read it — a bare perf_counter pair dies in a local variable."""

    def test_fires_on_perf_counter(self, tmp_path):
        src = """
            import time
            def run(plan):
                t0 = time.perf_counter()
                out = plan.run()
                dt = time.perf_counter() - t0
                return out, dt
        """
        got = _lint(tmp_path, src, "matrel_tpu/session.py")
        assert _rules(got) == ["ML006"]
        assert len(got) == 2                      # both call sites

    def test_fires_on_time_time_and_bare_import(self, tmp_path):
        src = """
            import time
            from time import perf_counter
            def run():
                a = time.time()
                b = perf_counter()
                return a, b
        """
        got = _lint(tmp_path, src, "matrel_tpu/serve/pipeline.py")
        assert _rules(got) == ["ML006"] and len(got) == 2

    def test_obs_and_autotune_exempt(self, tmp_path):
        src = """
            import time
            def measure():
                return time.perf_counter()
        """
        # the sanctioned timing homes: the obs layer itself and the
        # autotune measurement subsystem
        for rel in ("matrel_tpu/obs/trace.py",
                    "matrel_tpu/parallel/autotune.py"):
            assert _lint(tmp_path, src, rel) == []

    def test_out_of_package_ignored(self, tmp_path):
        src = """
            import time
            def bench():
                return time.time()
        """
        # bench harnesses / tools are entry points, not library code
        assert _lint(tmp_path, src, "chip_smoke.py") == []

    def test_suppression_with_justification(self, tmp_path):
        src = """
            import time
            def admit(q):
                q.put(time.perf_counter())  # matlint: disable=ML006 queue-wait timestamp
        """
        assert _lint(tmp_path, src, "matrel_tpu/serve/pipeline.py") == []

    def test_unrelated_time_methods_not_flagged(self, tmp_path):
        src = """
            def fmt(dt):
                return dt.time()            # datetime.time(), not timing
        """
        assert _lint(tmp_path, src, "matrel_tpu/io.py") == []


class TestML007BroadSwallow:
    def test_fires_on_except_exception_pass(self, tmp_path):
        src = """
            def load(path):
                try:
                    return open(path).read()
                except Exception:
                    pass
        """
        got = _lint(tmp_path, src, "matrel_tpu/io.py")
        assert _rules(got) == ["ML007"]

    def test_fires_on_bare_except_continue(self, tmp_path):
        src = """
            def drain(items):
                out = []
                for it in items:
                    try:
                        out.append(it())
                    except:
                        continue
                return out
        """
        got = _lint(tmp_path, src, "matrel_tpu/serve/x.py")
        assert _rules(got) == ["ML007"]

    def test_fires_on_base_exception_ellipsis(self, tmp_path):
        src = """
            def f(g):
                try:
                    g()
                except BaseException:
                    ...
        """
        got = _lint(tmp_path, src, "matrel_tpu/utils/x.py")
        assert _rules(got) == ["ML007"]

    def test_narrow_except_is_classification(self, tmp_path):
        src = """
            def load(path):
                try:
                    return open(path).read()
                except OSError:
                    pass
        """
        # naming the exception IS the taxonomy — out of scope
        assert _lint(tmp_path, src, "matrel_tpu/io.py") == []

    def test_logging_handler_not_flagged(self, tmp_path):
        src = """
            import logging
            def load(path):
                try:
                    return open(path).read()
                except Exception:
                    logging.warning("unreadable: %s", path)
        """
        assert _lint(tmp_path, src, "matrel_tpu/io.py") == []

    def test_typed_reraise_not_flagged(self, tmp_path):
        src = """
            from matrel_tpu.resilience.errors import CheckpointCorruption
            def load(path):
                try:
                    return open(path).read()
                except Exception as e:
                    raise CheckpointCorruption(str(e)) from e
        """
        assert _lint(tmp_path, src, "matrel_tpu/utils/x.py") == []

    def test_out_of_package_ignored(self, tmp_path):
        src = """
            def probe(f):
                try:
                    f()
                except Exception:
                    pass
        """
        # tools/bench harnesses collect failures their own way
        assert _lint(tmp_path, src, "tools/soak.py") == []

    def test_suppression_with_justification(self, tmp_path):
        src = """
            def emit(fn, rec):
                try:
                    fn(rec)
                except Exception:  # matlint: disable=ML007 never-fail obs sink
                    pass
        """
        assert _lint(tmp_path, src, "matrel_tpu/obs/sink.py") == []


class TestSuppression:
    def test_inline_disable_silences(self, tmp_path):
        src = """
            import jax
            def lower(x):
                jax.block_until_ready(x)  # matlint: disable=ML001 probe path
        """
        assert _lint(tmp_path, src, "matrel_tpu/ops/custom.py") == []

    def test_disable_is_per_code(self, tmp_path):
        src = """
            import jax
            def lower(x):
                jax.block_until_ready(x)  # matlint: disable=ML002 wrong code
        """
        got = _lint(tmp_path, src, "matrel_tpu/ops/custom.py")
        assert _rules(got) == ["ML001"]

    def test_unparseable_file_reports(self, tmp_path):
        got = _lint(tmp_path, "def broken(:\n", "matrel_tpu/ops/x.py")
        assert _rules(got) == ["ML000"]


class TestML008DevicePut:
    SRC = """
        import jax
        def place(x, sh):
            return jax.device_put(x, sh)
    """

    def test_fires_in_lowering_modules(self, tmp_path):
        for rel in ("matrel_tpu/executor.py",
                    "matrel_tpu/ops/custom.py",
                    "matrel_tpu/parallel/planner.py",
                    "matrel_tpu/serve/result_cache.py"):
            got = _lint(tmp_path, self.SRC, rel)
            assert "ML008" in _rules(got), rel

    def test_reshard_module_and_core_exempt(self, tmp_path):
        for rel in ("matrel_tpu/parallel/reshard.py",
                    "matrel_tpu/core/blockmatrix.py",
                    "matrel_tpu/utils/checkpoint.py",
                    "tools/some_harness.py"):
            assert "ML008" not in _rules(_lint(tmp_path, self.SRC,
                                               rel)), rel

    def test_compile_time_eval_sanctioned(self, tmp_path):
        src = """
            import jax
            def place_tables(tables, sh):
                with jax.ensure_compile_time_eval():
                    return [jax.device_put(t, sh) for t in tables]
        """
        got = _lint(tmp_path, src, "matrel_tpu/ops/custom.py")
        assert "ML008" not in _rules(got)

    def test_replicated_destination_sanctioned(self, tmp_path):
        src = """
            import jax
            from matrel_tpu.core.mesh import replicated
            def place(x, mesh):
                rep = replicated(mesh)
                a = jax.device_put(x, rep)
                b = jax.device_put(x, replicated(mesh))
                c = jax.device_put(x, device=rep)
                return a, b, c
        """
        got = _lint(tmp_path, src, "matrel_tpu/ops/custom.py")
        assert "ML008" not in _rules(got)

    def test_suppression_with_justification(self, tmp_path):
        src = """
            import jax
            def place(x, sh):
                return jax.device_put(x, sh)  # matlint: disable=ML008 host-built kernel table placement
        """
        assert _lint(tmp_path, src, "matrel_tpu/ops/custom.py") == []


class TestML009KernelSeam:
    def test_fires_on_pallas_call_in_ops_module(self, tmp_path):
        src = """
            from jax.experimental import pallas as pl
            def build(kern, spec, shape):
                return pl.pallas_call(kern, grid_spec=spec,
                                      out_shape=shape)
        """
        got = _lint(tmp_path, src, "matrel_tpu/ops/fancy_kernel.py")
        assert _rules(got) == ["ML009"]

    def test_registry_module_is_the_sanctioned_seam(self, tmp_path):
        src = """
            from jax.experimental import pallas as pl
            def build(kern, spec, shape):
                return pl.pallas_call(kern, grid_spec=spec,
                                      out_shape=shape)
        """
        assert _lint(tmp_path, src,
                     "matrel_tpu/ops/kernel_registry.py") == []

    def test_out_of_scope_modules_ignored(self, tmp_path):
        src = """
            from jax.experimental import pallas as pl
            def probe(kern, shape):
                return pl.pallas_call(kern, out_shape=shape)
        """
        # workloads/tools aren't executor dispatch surface
        assert _lint(tmp_path, src,
                     "matrel_tpu/workloads/pagerank.py") == []
        assert _lint(tmp_path, src, "tools/kernel_probe.py") == []

    def test_suppression_with_justification(self, tmp_path):
        src = """
            from jax.experimental import pallas as pl
            def build(kern, shape):
                return pl.pallas_call(kern, out_shape=shape)  # matlint: disable=ML009 legacy SpMV path unported this round
        """
        assert _lint(tmp_path, src, "matrel_tpu/ops/pallas_spmv.py") \
            == []

    def test_legacy_kernels_carry_justified_suppressions(self):
        # the porting worklist: every pre-registry kernel module lints
        # clean ONLY via its inline ML009 suppressions
        import os
        for mod in ("pallas_spmm.py", "pallas_spmv.py"):
            path = os.path.join(matlint.REPO, "matrel_tpu", "ops", mod)
            assert "disable=ML009" in open(path).read(), mod
            got = matlint.lint_file(path)
            assert [f for f in got if f.rule == "ML009"] == []


class TestML010JitSeam:
    def test_fires_on_jit_call_in_package(self, tmp_path):
        src = """
            import jax
            def runner(f):
                return jax.jit(f)
        """
        got = _lint(tmp_path, src, "matrel_tpu/serve/pipeline.py")
        assert _rules(got) == ["ML010"]

    def test_fires_on_jit_decorator(self, tmp_path):
        src = """
            import jax
            @jax.jit
            def step(x):
                return x * 2
        """
        got = _lint(tmp_path, src, "matrel_tpu/workloads/newwl.py")
        assert _rules(got) == ["ML010"]

    def test_executor_is_the_sanctioned_seam(self, tmp_path):
        src = """
            import jax
            def emit(fn):
                return jax.jit(fn)
        """
        assert _lint(tmp_path, src, "matrel_tpu/executor.py") == []

    def test_utils_and_harnesses_out_of_scope(self, tmp_path):
        src = """
            import jax
            @jax.jit
            def probe(x):
                return x + 1
        """
        assert _lint(tmp_path, src,
                     "matrel_tpu/utils/compat.py") == []
        assert _lint(tmp_path, src, "tools/some_probe.py") == []
        assert _lint(tmp_path, src, "chip_smoke.py") == []

    def test_suppression_with_justification(self, tmp_path):
        src = """
            import jax
            @jax.jit  # matlint: disable=ML010 workload runner cache, jitted once per static dims
            def step(x):
                return x * 2
        """
        assert _lint(tmp_path, src,
                     "matrel_tpu/workloads/newwl.py") == []

    def test_existing_sites_carry_justified_suppressions(self):
        # the porting worklist: the pre-seam jit sites lint clean ONLY
        # via their inline ML010 suppressions (the ML009 idiom)
        import os
        for mod in ("workloads/pagerank.py", "ops/spmv.py",
                    "parallel/autotune.py", "core/blockmatrix.py"):
            path = os.path.join(matlint.REPO, "matrel_tpu", *mod.split("/"))
            assert "disable=ML010" in open(path).read(), mod
            got = matlint.lint_file(path)
            assert [f for f in got if f.rule == "ML010"] == []


class TestML011UnboundedQueue:
    def test_fires_on_unbounded_deque_in_serve(self, tmp_path):
        src = """
            from collections import deque
            def build():
                q = deque()
                return q
        """
        got = _lint(tmp_path, src, "matrel_tpu/serve/newqueue.py")
        assert _rules(got) == ["ML011"]

    def test_fires_on_deque_with_iterable_but_no_maxlen(self,
                                                        tmp_path):
        # deque(iterable)'s first positional is the ITERABLE, not a
        # bound — the exact unbounded idiom the rule exists to catch
        src = """
            from collections import deque
            def build(items):
                return deque(items)
        """
        got = _lint(tmp_path, src, "matrel_tpu/serve/newqueue.py")
        assert _rules(got) == ["ML011"]

    def test_fires_on_unbounded_queue_in_serve(self, tmp_path):
        src = """
            import queue
            def build():
                return queue.Queue()
        """
        got = _lint(tmp_path, src, "matrel_tpu/serve/newqueue.py")
        assert _rules(got) == ["ML011"]

    def test_bounded_forms_pass(self, tmp_path):
        src = """
            import queue
            from collections import deque
            def build(n):
                a = deque(maxlen=n)
                b = deque([1, 2], n)
                c = queue.Queue(maxsize=n)
                d = queue.Queue(n)
                return a, b, c, d
        """
        assert _lint(tmp_path, src,
                     "matrel_tpu/serve/newqueue.py") == []

    def test_queues_outside_serve_out_of_scope(self, tmp_path):
        # the queue half is contextual: obs rings / host-side tooling
        # aren't on the admission path (the Thread half still applies
        # package-wide — keep the fixture thread-free)
        src = """
            from collections import deque
            def ring():
                return deque()
        """
        assert _lint(tmp_path, src, "matrel_tpu/obs/newring.py") == []
        assert _lint(tmp_path, src, "tools/newtool.py") == []

    def test_fires_on_thread_without_daemon(self, tmp_path):
        src = """
            import threading
            def start(fn):
                t = threading.Thread(target=fn)
                t.start()
                return t
        """
        got = _lint(tmp_path, src, "matrel_tpu/utils/newhelper.py")
        assert _rules(got) == ["ML011"]

    def test_thread_with_daemon_passes(self, tmp_path):
        src = """
            import threading
            def start(fn):
                t = threading.Thread(target=fn, daemon=True)
                t.start()
                return t
        """
        assert _lint(tmp_path, src,
                     "matrel_tpu/utils/newhelper.py") == []

    def test_suppression_with_justification(self, tmp_path):
        src = """
            from collections import deque
            def build():
                return deque()  # matlint: disable=ML011 bounded by the typed shed checks in put()
        """
        assert _lint(tmp_path, src,
                     "matrel_tpu/serve/newqueue.py") == []

    def test_admission_queue_carries_justified_suppressions(self):
        # the sanctioned sites: the AdmissionQueue's per-tenant deques
        # (bounded by typed shed logic, not maxlen — a maxlen deque
        # DROPS silently) and the pipeline's inflight deque (bounded
        # by the serve_max_inflight sync loop)
        import os
        for mod in ("admission.py", "pipeline.py"):
            path = os.path.join(matlint.REPO, "matrel_tpu", "serve",
                                mod)
            assert "disable=ML011" in open(path).read(), mod
            got = matlint.lint_file(path)
            assert [f for f in got if f.rule == "ML011"] == []


class TestML012ResultCacheSeam:
    def test_fires_on_entry_field_store(self, tmp_path):
        src = """
            def poke(ent, bm):
                ent.result = bm
                return ent
        """
        got = _lint(tmp_path, src, "matrel_tpu/serve/newplane.py")
        assert _rules(got) == ["ML012"]

    def test_fires_on_augassign_and_del(self, tmp_path):
        src = """
            def poke(ent):
                ent.err_bound += 1.0
                del ent.delta_rule
        """
        got = _lint(tmp_path, src, "matrel_tpu/session_helper.py")
        assert [f.rule for f in got] == ["ML012", "ML012"]

    def test_fires_on_internal_store_access(self, tmp_path):
        src = """
            def sneak(cache, key):
                cache._entries.pop(key, None)
                return cache._stale
        """
        got = _lint(tmp_path, src, "matrel_tpu/serve/newplane.py")
        assert _rules(got) == ["ML012"]
        assert len(got) == 2

    def test_replace_and_seam_calls_pass(self, tmp_path):
        # dataclasses.replace builds a NEW entry (the seam inserts
        # it), and the sanctioned seam methods are the whole point
        src = """
            import dataclasses
            def patch(cache, key, new_key, ent, bm, nb):
                new = dataclasses.replace(ent, result=bm, nbytes=nb)
                cache.apply_patch(key, new_key, new, 1 << 20)
                cache.rekey(key, new_key)
                cache.drop(key)
        """
        assert _lint(tmp_path, src,
                     "matrel_tpu/serve/newplane.py") == []

    def test_owning_module_exempt(self, tmp_path):
        src = """
            def inside(self, key):
                self._entries[key] = 1
                self._stale.clear()
        """
        assert _lint(tmp_path, src,
                     "matrel_tpu/serve/result_cache.py") == []

    def test_suppression_with_justification(self, tmp_path):
        src = """
            def poke(cache):
                return len(cache._entries)  # matlint: disable=ML012 test-only census helper, lock held by caller
        """
        assert _lint(tmp_path, src,
                     "matrel_tpu/serve/newplane.py") == []

    def test_ivm_plane_is_seam_clean(self):
        # the delta plane is the rule's raison d'être — it must route
        # every mutation through the seam with ZERO suppressions
        import os
        path = os.path.join(matlint.REPO, "matrel_tpu", "serve",
                            "ivm.py")
        assert "disable=ML012" not in open(path).read()
        got = matlint.lint_file(path)
        assert [f for f in got if f.rule == "ML012"] == []


class TestML013TimingAccumulation:
    def test_fires_on_latency_list_append(self, tmp_path):
        src = """
            def resolve(latencies, ms):
                latencies.append(ms)
        """
        got = _lint(tmp_path, src, "matrel_tpu/serve/newplane.py")
        assert _rules(got) == ["ML013"]

    def test_fires_on_ms_suffix_attr_and_extend(self, tmp_path):
        src = """
            class W:
                def feed(self, more):
                    self.queue_wait_ms.extend(more)
        """
        got = _lint(tmp_path, src, "matrel_tpu/session_helper.py")
        assert _rules(got) == ["ML013"]

    def test_fires_on_string_subscript_target(self, tmp_path):
        src = """
            def tally(row, ms):
                row["waits"].append(ms)
        """
        got = _lint(tmp_path, src, "matrel_tpu/serve/newplane.py")
        assert _rules(got) == ["ML013"]

    def test_non_timing_names_pass(self, tmp_path):
        src = """
            def collect(entries, pulled, it):
                entries.append(it)
                pulled.extend(entries)
                items = []
                items.append(it)
        """
        assert _lint(tmp_path, src,
                     "matrel_tpu/serve/newplane.py") == []

    def test_registry_api_passes(self, tmp_path):
        # the sanctioned path: record through the sketch/histogram API
        src = """
            from matrel_tpu.obs.metrics import REGISTRY
            def resolve(ms):
                REGISTRY.histogram("serve.latency_ms").observe(ms)
        """
        assert _lint(tmp_path, src,
                     "matrel_tpu/serve/newplane.py") == []

    def test_obs_package_exempt(self, tmp_path):
        src = """
            def aggregate(waits, ms):
                waits.append(ms)
        """
        assert _lint(tmp_path, src, "matrel_tpu/obs/history.py") == []

    def test_tools_out_of_scope(self, tmp_path):
        # harnesses ARE measurement (the ML006 autotune precedent)
        src = """
            def tally(row, ms):
                row["latencies"].append(ms)
        """
        assert _lint(tmp_path, src, "tools/traffic.py") == []

    def test_suppression_silences(self, tmp_path):
        src = """
            def observe(self, w):
                self._waits.append(w)  # matlint: disable=ML013 bounded controller window
        """
        assert _lint(tmp_path, src,
                     "matrel_tpu/resilience/brownout.py") == []


class TestML014FleetSeam:
    def test_fires_on_cross_slice_cache_write(self, tmp_path):
        src = """
            def poke(fleet, key, ent, cfg):
                fleet.slices[0].session._result_cache.put(
                    key, ent, cfg.result_cache_max_bytes)
        """
        got = _lint(tmp_path, src, "matrel_tpu/serve/newplane.py")
        assert _rules(got) == ["ML014"]

    def test_fires_on_foreign_session_invalidate(self, tmp_path):
        src = """
            def drop_all(other, ids):
                other.session._result_cache.invalidate_deps(ids)
        """
        got = _lint(tmp_path, src, "matrel_tpu/serve/newplane.py")
        assert _rules(got) == ["ML014"]

    def test_own_cache_mutation_passes(self, tmp_path):
        src = """
            class Plane:
                def insert(self, key, ent, cfg):
                    self.session._result_cache.put(
                        key, ent, cfg.result_cache_max_bytes)
                def drop(self, key):
                    self._result_cache.drop(key)
        """
        assert _lint(tmp_path, src,
                     "matrel_tpu/serve/newplane.py") == []

    def test_sess_alias_passes(self, tmp_path):
        # the IVM plane's idiom: sess = self.session; sess._result_
        # cache.apply_patch(...) — a session mutating its OWN cache
        src = """
            def patch(self, key, new_key, ent, cfg):
                sess = self.session
                ok = sess._result_cache.apply_patch(
                    key, new_key, ent, cfg.result_cache_max_bytes)
                return ok
        """
        assert _lint(tmp_path, src,
                     "matrel_tpu/serve/newplane.py") == []

    def test_reads_and_lookups_pass(self, tmp_path):
        # the rule pins MUTATION: the fleet's hit-anywhere protocol
        # reads other caches through the public lookup surface
        src = """
            def peek(other, key):
                return other.session._result_cache.lookup(key)
        """
        assert _lint(tmp_path, src,
                     "matrel_tpu/serve/newplane.py") == []

    def test_fleet_module_is_the_sanctioned_seam(self, tmp_path):
        src = """
            def replicate(target, key, ent, cfg):
                target.session._result_cache.put(
                    key, ent, cfg.result_cache_max_bytes)
        """
        assert _lint(tmp_path, src,
                     "matrel_tpu/serve/fleet.py") == []

    def test_out_of_scope_modules_pass(self, tmp_path):
        src = """
            def poke(fleet, key, ent, cfg):
                fleet.slices[0].session._result_cache.put(
                    key, ent, cfg.result_cache_max_bytes)
        """
        assert _lint(tmp_path, src, "matrel_tpu/obs/whatever.py") == []


class TestML015ProvenanceSeam:
    def test_fires_on_attribute_store(self, tmp_path):
        src = """
            def stamp(ent, key_hash):
                ent.provenance = {"schema": 1, "key_hash": key_hash}
        """
        got = _lint(tmp_path, src, "matrel_tpu/serve/newplane.py")
        assert _rules(got) == ["ML015"]

    def test_fires_on_subscript_store(self, tmp_path):
        # the attrs-dict route around the attribute check
        src = """
            def stamp(attrs, rec):
                attrs["provenance"] = {"query_id": rec.query_id}
        """
        got = _lint(tmp_path, src, "matrel_tpu/session.py")
        assert _rules(got) == ["ML015"]

    def test_fires_on_with_attrs_keyword(self, tmp_path):
        # the immutable-expr route: threading a hand-built stamp onto
        # a substitution leaf
        src = """
            def leaf_with_stamp(node, stamp):
                return node.with_attrs(provenance=stamp)
        """
        got = _lint(tmp_path, src, "matrel_tpu/executor.py")
        assert _rules(got) == ["ML015"]

    def test_fires_on_del(self, tmp_path):
        src = """
            def scrub(ent):
                del ent.provenance
        """
        got = _lint(tmp_path, src, "matrel_tpu/serve/fleet.py")
        assert _rules(got) == ["ML015"]

    def test_reads_and_calls_pass(self, tmp_path):
        # the sanctioned idiom: modules READ stamps and CALL the
        # ledger's writers; only the ledger builds the dict
        src = """
            def serve(sess, ent, key, parent):
                if ent.provenance is not None:
                    ancestry = ent.provenance.get("query_id")
                sess._prov.stamp_entry(ent, "fleet_replica", parent)
                return ent.provenance
        """
        assert _lint(tmp_path, src,
                     "matrel_tpu/serve/newplane.py") == []

    def test_ledger_module_is_the_sanctioned_seam(self, tmp_path):
        src = """
            def stamp_entry(ent, path, parent):
                ent.provenance = {"schema": 1, "path": path}
        """
        assert _lint(tmp_path, src,
                     "matrel_tpu/obs/provenance.py") == []

    def test_out_of_scope_modules_pass(self, tmp_path):
        # tools/ and tests build fixture stamps freely — the rule pins
        # the library's serve path, not the harnesses around it
        src = """
            def fixture(ent):
                ent.provenance = {"schema": 1}
        """
        assert _lint(tmp_path, src, "tools/some_drill.py") == []


class TestML016TemplateKeying:
    """The MQO plane's keying contract (ISSUE 17): plan-template /
    CSE caches key by the canonical leaf-abstracted structural key
    (mqo.template_key), never id()/uid/spec — the ML005 hazard class
    extended to entries that outlive the queries that built them. The
    fixtures prove the rule would catch each regression shape, and the
    real module must scan clean."""

    def test_id_keyed_template_store_fires(self, tmp_path):
        src = """
            class MqoState:
                def __init__(self):
                    self.templates = {}
                def put(self, root, plan):
                    self.templates[id(root)] = plan
        """
        got = _lint(tmp_path, src, "matrel_tpu/serve/mqo.py")
        assert _rules(got) == ["ML016"]

    def test_uid_keyed_hoist_get_fires(self, tmp_path):
        src = """
            def probe(hoist_cache, node):
                return hoist_cache.get(node.uid)
        """
        got = _lint(tmp_path, src, "matrel_tpu/session.py")
        assert _rules(got) == ["ML016"]

    def test_spec_keyed_template_fires(self, tmp_path):
        # spec objects hash by identity or not at all — the original
        # ML005 shape, caught on template-named dicts too
        src = """
            def put(tpl_entries, m, plan):
                tpl_entries[m.spec] = plan
        """
        got = _lint(tmp_path, src, "matrel_tpu/serve/mqo.py")
        assert _rules(got) == ["ML016"]

    def test_structural_key_clean(self, tmp_path):
        # the sanctioned idiom: key derived from template_key, a
        # plain string whose equality IS plan equivalence
        src = """
            def put(templates, prefix, akey, entry):
                templates[prefix + akey] = entry
            def probe(templates, key):
                return templates.get(key)
        """
        assert _lint(tmp_path, src, "matrel_tpu/serve/mqo.py") == []

    def test_local_identity_class_map_clean(self, tmp_path):
        # first-occurrence identity classes inside one template_key
        # walk die with the walk — not a cache, not template-named,
        # exactly why the rule scopes by NAME
        src = """
            def template_key(leaves):
                classes = {}
                toks = [classes.setdefault(id(m), len(classes))
                        for m in leaves]
                return toks
        """
        assert _lint(tmp_path, src, "matrel_tpu/serve/mqo.py") == []

    def test_real_mqo_module_is_ml016_clean(self):
        import os
        got = matlint.lint_file(
            os.path.join(matlint.REPO, "matrel_tpu", "serve",
                         "mqo.py"))
        assert [f for f in got if f.rule == "ML016"] == []


class TestML017LockSeam:
    def test_fires_on_bare_lock(self, tmp_path):
        src = """
            import threading
            class Plane:
                def __init__(self):
                    self._lock = threading.Lock()
        """
        got = _lint(tmp_path, src, "matrel_tpu/serve/newplane.py")
        assert _rules(got) == ["ML017"]

    def test_fires_on_bare_rlock_module_level(self, tmp_path):
        src = """
            from threading import RLock
            _LOCK = RLock()
        """
        got = _lint(tmp_path, src, "matrel_tpu/obs/newobs.py")
        assert _rules(got) == ["ML017"]

    def test_seam_construction_passes(self, tmp_path):
        # the sanctioned idiom: named construction through the seam —
        # the lock lands in lockcheck's inventory and lockdep's graph
        src = """
            from matrel_tpu.utils import lockdep
            class Plane:
                def __init__(self):
                    self._lock = lockdep.make_lock("serve.newplane")
        """
        assert _lint(tmp_path, src,
                     "matrel_tpu/serve/newplane.py") == []

    def test_condition_and_event_pass(self, tmp_path):
        # only Lock/RLock construction is seamed: Condition wraps an
        # already-seamed lock, Event's internal lock guards no
        # package state
        src = """
            import threading
            from matrel_tpu.utils import lockdep
            class Plane:
                def __init__(self):
                    self._lock = lockdep.make_lock("serve.cvplane")
                    self._cv = threading.Condition(self._lock)
                    self._stop = threading.Event()
        """
        assert _lint(tmp_path, src,
                     "matrel_tpu/serve/newplane.py") == []

    def test_lockdep_module_is_the_sanctioned_seam(self, tmp_path):
        src = """
            import threading
            _STATE_LOCK = threading.Lock()
            def make_lock(name):
                return threading.Lock()
        """
        assert _lint(tmp_path, src,
                     "matrel_tpu/utils/lockdep.py") == []

    def test_out_of_scope_modules_pass(self, tmp_path):
        # tools/tests spin up fixture locks freely — the seam pins the
        # package's lock plane, not the harnesses around it
        src = """
            import threading
            L = threading.Lock()
        """
        assert _lint(tmp_path, src, "tools/some_drill.py") == []

    def test_suppression_silences(self, tmp_path):
        src = """
            import threading
            _LOCK = threading.Lock()  # matlint: disable=ML017 fixture: raw by necessity
        """
        assert _lint(tmp_path, src, "matrel_tpu/obs/newobs.py") == []


class TestML018CoeffSeam:
    def test_fires_on_drift_qualified_call(self, tmp_path):
        src = """
            from matrel_tpu.obs import drift
            def rank(cfg):
                table = drift.load_table(drift.table_path(cfg))
                return table
        """
        got = _lint(tmp_path, src, "matrel_tpu/serve/newrank.py")
        assert _rules(got) == ["ML018"]

    def test_fires_on_import_from_drift(self, tmp_path):
        src = """
            from matrel_tpu.obs.drift import load_table, table_path
            def rank(cfg):
                return load_table(table_path(cfg))
        """
        got = _lint(tmp_path, src, "matrel_tpu/parallel/newrank.py")
        assert _rules(got) == ["ML018"]

    def test_seam_consult_passes(self, tmp_path):
        # the sanctioned idiom: memoized, epoch-stamped reads through
        # parallel/coeffs.py (table_path/shape_class stay legal — they
        # are addressing, not reads)
        src = """
            from matrel_tpu.obs import drift
            from matrel_tpu.parallel import coeffs
            def rank(cfg, strategy, dims):
                return coeffs.strategy_row(
                    strategy, drift.shape_class(dims), "cpu",
                    drift.table_path(cfg))
        """
        assert _lint(tmp_path, src,
                     "matrel_tpu/serve/newrank.py") == []

    def test_autotune_table_reader_passes(self, tmp_path):
        # parallel/autotune.py has its own same-named load_table for
        # the AUTOTUNE table — a different store with its own seam;
        # only drift-qualified consults are in ML018's domain
        src = """
            import json
            def load_table(path):
                with open(path) as f:
                    return json.load(f)
            def consult(path):
                return load_table(path)
        """
        assert _lint(tmp_path, src,
                     "matrel_tpu/parallel/newtune.py") == []

    def test_obs_modules_out_of_scope(self, tmp_path):
        # the auditor/controller plane OWNS the table — obs/ reads and
        # writes it directly by design
        src = """
            from matrel_tpu.obs import drift
            def audit(cfg):
                return drift.load_table(drift.table_path(cfg))
        """
        assert _lint(tmp_path, src, "matrel_tpu/obs/newaudit.py") == []


class TestML019DurableIoSeam:
    def test_fires_on_open_in_serve(self, tmp_path):
        src = """
            def persist(path, payload):
                with open(path, "w") as f:
                    f.write(payload)
        """
        got = _lint(tmp_path, src, "matrel_tpu/serve/newstate.py")
        assert _rules(got) == ["ML019"]

    def test_fires_on_np_save_and_os_replace(self, tmp_path):
        src = """
            import os
            import numpy as np
            def persist(path, arr):
                np.save(path + ".tmp", arr)
                os.replace(path + ".tmp", path)
            def thaw(path):
                return np.load(path)
        """
        got = _lint(tmp_path, src, "matrel_tpu/serve/newcache.py")
        assert [f.rule for f in got] == ["ML019"] * 3

    def test_fires_on_json_dump(self, tmp_path):
        src = """
            import json
            def persist(f, payload):
                json.dump(payload, f)
        """
        got = _lint(tmp_path, src, "matrel_tpu/serve/newmeta.py")
        assert _rules(got) == ["ML019"]

    def test_spill_seam_exempt(self, tmp_path):
        # the sanctioned seam: serve/spill.py IS the one writer
        src = """
            import os
            import numpy as np
            def _write_artifact(path, arr):
                with open(path + ".tmp", "wb") as f:
                    np.save(f, arr)
                os.replace(path + ".tmp", path)
        """
        assert _lint(tmp_path, src, "matrel_tpu/serve/spill.py") == []

    def test_outside_serve_out_of_scope(self, tmp_path):
        # checkpoint/obs/tools keep their own IO discipline — the
        # seam rule scopes to the serving plane only
        src = """
            import json
            def persist(path, payload):
                with open(path, "w") as f:
                    json.dump(payload, f)
        """
        assert _lint(tmp_path, src,
                     "matrel_tpu/utils/newstore.py") == []

    def test_in_memory_dict_ops_pass(self, tmp_path):
        # same tails, different owners: dict.pop/list ops and
        # non-IO modules' save/load verbs are not in the rule's
        # vocabulary
        src = """
            def evict(cache, key):
                return cache.pop(key, None)
            def save(state, snapshot):
                state.update(snapshot)
        """
        assert _lint(tmp_path, src,
                     "matrel_tpu/serve/newpolicy.py") == []

    def test_coeffs_module_is_the_sanctioned_seam(self, tmp_path):
        src = """
            from matrel_tpu.obs import drift
            def _payload(path):
                return drift.load_table(path)
        """
        assert _lint(tmp_path, src,
                     "matrel_tpu/parallel/coeffs.py") == []


def test_repo_lints_clean():
    """`make lint`'s contract, enforced from inside tier-1: the whole
    default scan set (package, tools, examples, bench harnesses) has
    zero unsuppressed findings."""
    findings = matlint.lint_paths()
    assert findings == [], "\n".join(f.render() for f in findings)


def test_rule_catalogue_documented():
    # every rule carries an ID and a docstring (the catalogue the docs
    # and --list-rules render); IDs are unique
    ids = [r.id for r in matlint.RULES]
    assert len(ids) == len(set(ids))
    for r in matlint.RULES:
        assert r.id.startswith("ML") and r.__doc__
        assert r.id in r.__doc__.strip().splitlines()[0]
