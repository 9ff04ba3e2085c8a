"""Seconds in the program's retained ``train/init_state`` spans up to the ready
stamp: the init program traced, compiled or loaded, and run."""
import startup_trace


def read(facts):
    return startup_trace.summary(facts).get("init_state_s")
