"""Device seconds per cold solve of the power iteration that sets the step
size: the own time of the window's ops under the program's
`power_iteration` scope (their `tf_op` in the trace's op metadata;
core/sharding power_fn), over the window's solves.  It cuts the same
device time by phase that the oracle metrics cut by sub-layer."""
from chipbench import scopes


def read(rec):
    return scopes.per_solve(rec, "power_iteration")
