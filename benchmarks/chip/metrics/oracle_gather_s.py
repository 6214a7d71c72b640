"""Device seconds per cold solve of the oracle's gather of A^T lam: the own
time of the window's ops under the program's `oracle.gather` scope (their
`tf_op` in the trace's op metadata; core/objective._gather_at_lam and the
fused path's gather in kernels/ops.py), over the window's solves."""
from chipbench import scopes


def read(rec):
    return scopes.per_solve(rec, "oracle.gather")
