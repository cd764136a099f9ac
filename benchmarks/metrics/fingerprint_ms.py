"""Session and executor dispatch: per PageRank query, the time in the
program's ``matrel.pagerank.fingerprint`` spans (the content hash of
the edge arrays that keys the prepared-plan cache), summed; the
median."""

import statistics

from benchmarks import program_spans


def read(run, records=None):
    found = program_spans.window(run, records)
    if found is None:
        return None
    records, roots = found
    per_query = [sum(program_spans.ms(r) for r in records
                     if r["name"] == "matrel.pagerank.fingerprint"
                     and r["qid"] == root["qid"])
                 for root in roots if root["name"] == "matrel.pagerank"]
    if not per_query:
        run.say("program spans: no matrel.pagerank in the window")
        return None
    return statistics.median(per_query)
