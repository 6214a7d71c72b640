"""Host seconds of the program's `normalize_rows` span
(core/objective.normalize_rows: Jacobi row scaling of the slabs) in
set-up, from the program's tracer."""
from chipbench import scopes


def read(rec):
    return scopes.span_seconds(rec, "normalize_rows")
