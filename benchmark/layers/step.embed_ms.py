"""Device time of one train step inside the embedding modules, forward and
backward (``parts/<builder>.json``, part ``embed``), per step run on chip 0."""
import program_trace


def read(facts):
    return program_trace.part_ms(facts, "embed")
