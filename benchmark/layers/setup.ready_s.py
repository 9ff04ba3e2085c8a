"""Seconds from the first import of ``raydp_tpu`` to the end of the last epoch
that paid for a program (the program's gauge ``train/ready_seconds``): where
start-up ended by the program's own rule."""
import startup_trace


def read(facts):
    return startup_trace.summary(facts).get("ready_s")
