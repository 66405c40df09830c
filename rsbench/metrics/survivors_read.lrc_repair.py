"""Survivor fragments read per rebuilt fragment in the traced window:
the change of ``rs_repair_survivors_read_total`` over that of
``rs_repair_chunks_rebuilt_total`` (6 for a local plan, 12 for a global
one)."""

from rsbench.plan_counters import counter_values


def read(run):
    read_ = counter_values(run, "rs_repair_survivors_read_total")
    rebuilt = counter_values(run, "rs_repair_chunks_rebuilt_total")
    if not read_ or not rebuilt or sum(rebuilt.values()) <= 0:
        return None
    return sum(read_.values()) / sum(rebuilt.values())
