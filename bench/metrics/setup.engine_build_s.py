"""Seconds of the engine build (EngineCore: schedules, parameters, the
week and the state on the device), compiles excluded: host clock."""


def read(run):
    return run.engine_build_s
