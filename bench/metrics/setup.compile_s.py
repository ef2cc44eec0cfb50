"""Seconds of XLA and Mosaic backend compiles during set-up, from JAX's
compile events."""


def read(run):
    return run.compile_s
