"""Per-layer metric: capacity escalations per join in the window, from the
``escalations`` of each join's result (the program's own count of the
re-runs its overflow contract forced)."""


def read(run):
    joins = run.work.get("joins")
    escalations = run.counters.get("join.escalations")
    if not joins or escalations is None:
        return None
    return escalations / joins
