"""Device time of one train step in the final norm, the output head and the
loss (``lm_head``, ``ln_final``, scope ``part:loss``), forward and backward
(part ``head``), per step run on chip 0."""
import program_trace


def read(facts):
    return program_trace.part_ms(facts, "head")
