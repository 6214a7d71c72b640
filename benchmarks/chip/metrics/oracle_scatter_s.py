"""Device seconds per cold solve of the oracle's scatter-add of A x: the own
time of the window's ops under the program's `oracle.scatter` scope (their
`tf_op` in the trace's op metadata; core/objective._segment_sum_ax), over
the window's solves."""
from chipbench import scopes


def read(rec):
    return scopes.per_solve(rec, "oracle.scatter")
