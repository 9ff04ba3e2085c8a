"""Python's trace and the lowering to MLIR of the programs built by the ready
stamp under a span of the program (``compile_records()``): the share of
start-up that no cache shortens."""
import startup_trace


def read(facts):
    return startup_trace.summary(facts).get("trace_lower_s")
