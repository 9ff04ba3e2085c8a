"""Device time of one train step inside the feature interaction, forward and
backward (part ``interaction``), per step run on chip 0."""
import program_trace


def read(facts):
    return program_trace.part_ms(facts, "interaction")
