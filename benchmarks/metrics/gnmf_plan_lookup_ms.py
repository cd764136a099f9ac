"""Session and executor dispatch: ``plan_lookup_ms.py``'s reader on the
GNMF cell's spans, every update a query root (gnmf_spans.per_update): the
median ``matrel.plan`` span, here the key walk and the template probe
over leaves that change every call."""

from benchmarks.metrics import gnmf_spans


def read(run):
    return gnmf_spans.accepted(run, "plan_lookup_ms").read(
        gnmf_spans.per_update(run))
