"""Seconds to load the twin and its packed week from the cache (or to
build them, on a checkout's first run): host clock."""


def read(run):
    return run.twin_s
