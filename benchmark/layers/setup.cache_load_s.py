"""The backend events of the programs built by the ready stamp that the
persistent cache held (``cache`` ``hit``): the read and the load."""
import startup_trace


def read(facts):
    return startup_trace.summary(facts).get("cache_load_s")
