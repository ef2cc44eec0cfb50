"""Device milliseconds per scenario-day of the interaction kernel: the
custom-call (Mosaic) operations of the traced window."""


def read(run):
    if run.trace is None or run.scenario_days == 0:
        return None
    s = run.trace["kind_s"].get("kernel", 0.0)
    return s * 1e3 / run.scenario_days if s > 0 else None
