"""Milliseconds per cadence of the program's `ingest` span
(service/scheduler.py: host delta application and scatter-plan building),
from the program's tracer, over the window's cadences."""


def read(rec):
    spans = rec["run"].get("ingest_s") if rec["run"].get("driver") == "service" else None
    if not spans:
        return None
    return 1e3 * sum(spans) / len(spans)
