"""Share of the oracle's gather of A^T lam that the one-hot gather kernel
does, over the cold-solve window: 100 × the own device time of the ops
under a `onehot` scope nested in `oracle.gather` (the `dual_gather`
kernel; kernels/ops.onehot_gather) ÷ that of the ops under
`oracle.gather`.  A `onehot` scope anywhere else is not counted.  None
where no op carries `oracle.gather/.../onehot`: a program that gathers
through XLA alone."""
import re

from chipbench import scopes

_ONEHOT = re.compile(r"(^|/)oracle\.gather/(.+/)?onehot(/|:|$)")


def read(rec):
    gather = scopes.per_solve(rec, "oracle.gather")
    if gather is None:
        return None
    paths = {op: path for op, path in scopes.tf_ops(chips=rec["chips"]).items()
             if _ONEHOT.search(path)}
    if not paths:
        return None
    onehot = scopes.scope_seconds(rec["trace"], paths, "onehot", rec["chips"])
    return 100.0 * onehot / rec["run"]["solves"] / gather if onehot > 0 else None
