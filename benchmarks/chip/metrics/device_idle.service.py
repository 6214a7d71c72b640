"""Share of the cadence window in which no operation ran on the chip
(profiler trace: 1 - union of the chip's op intervals / window), averaged
over the cell's chips."""
from chipbench import trace as tr


def read(rec):
    t = rec["trace"]
    if t is None or rec["run"].get("driver") != "service" or t.window_ns <= 0:
        return None
    busy = sum(tr.busy_ns(t, c) for c in range(rec["chips"])) / rec["chips"]
    return 100.0 * (1.0 - busy / t.window_ns)
