"""A ring of program spans whose answers are known by hand: what
``matrel_tpu.obs.trace.profile_spans()`` would hold after a traced
window of two SQL queries and one more call after it, and of two
PageRank queries."""

import types

MS = 1_000_000      # nanoseconds
T0 = 1_790_000_000_000_000_000


def rec(name, start_ms, dur_ms, span_id, parent_id, qid, **attrs):
    return {"name": name, "start_ns": T0 + int(start_ms * MS),
            "end_ns": T0 + int((start_ms + dur_ms) * MS),
            "span_id": span_id, "parent_id": parent_id, "qid": qid,
            "tid": 1, "attrs": attrs}


def sql_ring():
    """Queries at 0, 10 and (after the window) 20 ms: sql 0.1, compute
    {1.0, 2.0, 9.0} = plan {0.2, 0.4, 0.2} + dispatch {0.5, 1.0, 0.5} +
    self {0.3, 0.6, 8.3}, fetch {0.8, 1.2, 0.8}; the third compiles."""
    out, sid = [], 0
    for k, (t, comp, plan, disp, fetch) in enumerate(
            [(0, 1.0, 0.2, 0.5, 0.8), (10, 2.0, 0.4, 1.0, 1.2),
             (20, 9.0, 0.2, 0.5, 0.8)]):
        q = 3 * k
        out.append(rec("matrel.sql", t, 0.1, sid + 1, None, q + 1, chars=9))
        out.append(rec("matrel.compute", t + 0.2, comp, sid + 2, None,
                       q + 2, root_kind="agg", path="fast"))
        out.append(rec("matrel.plan", t + 0.3, plan, sid + 3, sid + 2,
                       q + 2, hit=k < 2))
        if k == 2:
            out.append(rec("matrel.compile", t + 0.6, 8.0, sid + 6,
                           sid + 2, q + 2, executors=["xla"]))
        out.append(rec("matrel.dispatch", t + 0.3 + plan, disp, sid + 4,
                       sid + 2, q + 2, executors=["xla"]))
        out.append(rec("matrel.fetch", t + 0.3 + comp, fetch, sid + 5,
                       None, q + 3, bytes=64))
        sid += 6
    return sorted(out, key=lambda r: r["end_ns"])   # as the ring holds them


def pagerank_ring():
    """Two queries of 200 and 260 ms: the first builds its plan (a miss)
    and hashes once for 150 ms, the second hashes twice (80 + 100)."""
    return [
        rec("matrel.pagerank.fingerprint", 1, 150, 2, 1, 1, bytes=80),
        rec("matrel.pagerank.plan", 152, 20, 3, 1, 1, hit=False),
        rec("matrel.pagerank.dispatch", 173, 20, 4, 1, 1),
        rec("matrel.pagerank", 0, 200, 1, None, 1, impl="compact"),
        rec("matrel.pagerank.fingerprint", 1001, 80, 6, 5, 2, bytes=80),
        rec("matrel.pagerank.fingerprint", 1082, 100, 7, 5, 2, bytes=80),
        rec("matrel.pagerank.plan", 1183, 1, 8, 5, 2, hit=True),
        rec("matrel.pagerank.dispatch", 1185, 20, 9, 5, 2),
        rec("matrel.pagerank", 1000, 260, 5, None, 2, impl="compact"),
    ]


def run_of(n_queries, said=None, window_ms=None):
    """What a reader is handed, for a trace of ``n_queries`` queries in a
    window of ``window_ms`` (as default the rings' own: a query every 10
    ms and 4 ms for the last of the SQL ring, 1000 and 300 of the
    PageRank ring's)."""
    if window_ms is None:
        window_ms = 10 * (n_queries - 1) + 4
    return types.SimpleNamespace(
        reduced={"queries": [{}] * n_queries,
                 "window_s": window_ms * 1e-3} if n_queries else None,
        say=(said.append if said is not None else lambda line: None))
