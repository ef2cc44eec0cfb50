"""Device-busy milliseconds per scenario-day in the traced window: the
union of the device's operation intervals over the scenario-days the
window completed."""


def read(run):
    if run.trace is None or run.scenario_days == 0:
        return None
    return run.trace["busy_s"] * 1e3 / run.scenario_days
