"""Persistent-cache entries this run wrote during set-up: programs that
were not found in the cache."""


def read(ctx):
    return ctx["counters"]["cache_misses"]
