"""Seconds XLA spent compiling during set-up (``obs.trace`` counter)."""


def read(ctx):
    return ctx["counters"]["xla_compile_s"]
