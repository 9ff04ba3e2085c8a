"""Share of the traced window in which chip 0 ran nothing and no span of the
program was open on any thread."""
import program_trace


def read(facts):
    return program_trace.summary(facts).get("idle_unattributed_share")
