"""Device milliseconds per query batch: the summed device time of the query
program (serving/duals.py, the jitted function `q`, whose programs are
named `jit_q`) in the profiler trace's module line over the window, divided
by the batches due in the window."""
import re

from chipbench import trace as tr

MODULE = re.compile(r"^jit_q(\W|$)")


def read(rec):
    run, t = rec["run"], rec["trace"]
    if t is None or run.get("driver") != "service" or not run.get("query_batches"):
        return None
    lo, hi = t.window
    events = [e for c in range(rec["chips"]) for e in t.modules(c)
              if MODULE.match(e.name)]
    if not events:
        return None
    busy = sum(s1 - s0 for s0, s1 in tr.union(events, lo, hi))
    return busy * 1e-6 / run["query_batches"]
