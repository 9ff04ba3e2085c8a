"""The backend events of the programs built by the ready stamp that the
persistent cache did not hold (``cache`` ``miss`` or ``uncached``): XLA's and
Mosaic's compile."""
import startup_trace


def read(facts):
    return startup_trace.summary(facts).get("backend_compile_s")
