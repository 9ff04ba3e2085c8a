"""Share of the traced window the step loop's thread spent inside the program's
``train/step`` spans, the dispatch of the jitted step."""
import program_trace


def read(facts):
    return program_trace.summary(facts).get("dispatch_share")
