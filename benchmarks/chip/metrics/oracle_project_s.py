"""Device seconds per cold solve of the XLA oracle's primal candidate and
simplex projection: the own time of the window's ops under the program's
`oracle.project` scope (their `tf_op` in the trace's op metadata;
core/objective.MatchingObjective.primal_candidate), over the window's
solves."""
from chipbench import scopes


def read(rec):
    return scopes.per_solve(rec, "oracle.project")
