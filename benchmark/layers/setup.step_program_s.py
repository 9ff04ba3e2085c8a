"""Seconds in the program's retained ``train/first_dispatch`` spans up to the
ready stamp (``predict_step``'s, after the window, is not in it): each step
program's trace, compile or load, and first queueing."""
import startup_trace


def read(facts):
    return startup_trace.summary(facts).get("step_program_s")
