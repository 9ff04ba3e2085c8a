"""How many programs built by the ready stamp were compiled and written to the
persistent cache (``cache`` ``miss``: a warm run would have loaded them): 0 in
a warm run of an unchanged tree with a seed it has seen; the programs too small
for the cache to take are ``uncached`` and listed in the report."""
import startup_trace


def read(facts):
    return startup_trace.summary(facts).get("cache_miss_programs")
