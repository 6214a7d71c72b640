"""Share of the oracle's scatter-add of A x that the one-hot scatter kernel
does, over the cold-solve window: 100 × the own device time of the ops
under a `onehot` scope nested in `oracle.scatter` (the `dual_scatter`
kernel; kernels/ops.dual_scatter) ÷ that of the ops under
`oracle.scatter`.  A `onehot` scope anywhere else is not counted.  None
where no op carries `oracle.scatter/.../onehot`: a program that
scatter-adds through XLA alone."""
import re

from chipbench import scopes

_ONEHOT = re.compile(r"(^|/)oracle\.scatter/(.+/)?onehot(/|:|$)")


def read(rec):
    scatter = scopes.per_solve(rec, "oracle.scatter")
    if scatter is None:
        return None
    paths = {op: path for op, path in scopes.tf_ops(chips=rec["chips"]).items()
             if _ONEHOT.search(path)}
    if not paths:
        return None
    onehot = scopes.scope_seconds(rec["trace"], paths, "onehot", rec["chips"])
    return 100.0 * onehot / rec["run"]["solves"] / scatter if onehot > 0 else None
