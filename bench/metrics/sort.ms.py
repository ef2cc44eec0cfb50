"""Device milliseconds per scenario-day of XLA sorts in the traced window:
the seeding and testing order statistics and the kernel's schedule
compaction."""


def read(run):
    if run.trace is None or run.scenario_days == 0:
        return None
    s = run.trace["kind_s"].get("sort", 0.0)
    return s * 1e3 / run.scenario_days if s > 0 else None
