"""Host seconds of the program's `bucketize` span (instances/buckets.py:
packing the edge list into bucketed slabs) in set-up, from the program's
tracer."""
from chipbench import scopes


def read(rec):
    return scopes.span_seconds(rec, "bucketize")
