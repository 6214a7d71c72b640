"""Device milliseconds per cold solve of the solver's collectives: the own
time of the window's ops under the program's `dist.psum` scope (their
`tf_op` in the trace's op metadata; core/sharding.scoped_psum around every
all-reduce of the column-sharded solve), averaged over the chips, over the
window's solves.  The solve is a serial loop, so an all-reduce hides behind
nothing: this is the collective's cost plus any wait of a fast shard for a
slow one.  `power_iteration_s` holds the power steps' part of it too.  None
where no op carries the scope: a program that does not name it, or a
one-chip mesh, where the collective is elided."""
from chipbench import scopes


def read(rec):
    seconds = scopes.per_solve(rec, "dist.psum")
    return None if seconds is None else 1e3 * seconds
