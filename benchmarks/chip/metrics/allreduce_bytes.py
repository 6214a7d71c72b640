"""Bytes each shard contributes to the all-reduces of one cold solve: the
program's counter `dist_allreduce_bytes_total` over `dist_solves_total`
(repro.telemetry's registry; core/sharding.DistributedMaximizer.solve).  It
depends on the dual dimension and the schedule, not on sources.  None where
the program does not keep the counter."""


def read(rec):
    if rec["run"].get("driver") != "solve":
        return None
    from repro import telemetry

    reg = telemetry.get_registry()
    solves = reg.counter_total("dist_solves_total")
    total = reg.counter_total("dist_allreduce_bytes_total")
    return total / solves if solves and total else None
