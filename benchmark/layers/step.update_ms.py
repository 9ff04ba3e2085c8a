"""Device time of one train step under the program's ``part:update`` and
``part:grad_norm`` scopes (the optimizer update XLA left unfused and the
gradient norm), per step run on chip 0."""
import program_trace


def read(facts):
    return program_trace.part_ms(facts, "update")
