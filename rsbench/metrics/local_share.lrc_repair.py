"""Share (%) of the fragments rebuilt in the traced window that the
repair planner rebuilt from a local group (fewer than k survivors): the
change of ``rs_repair_plans_total{plan}`` over the window."""

from rsbench.plan_counters import counter_values


def read(run):
    plans = counter_values(run, "rs_repair_plans_total")
    total = sum(plans.values()) if plans else 0
    return 100.0 * plans.get('{plan="local"}', 0) / total if total else None
