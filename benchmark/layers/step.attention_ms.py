"""Device time of one train step inside the attention modules and their layer
norm, forward and backward (part ``attention``), per step run on chip 0."""
import program_trace


def read(facts):
    return program_trace.part_ms(facts, "attention")
