"""Device time of one train step in a looped LM's exits BEFORE the last:
each one's gate, its head's product, its cross-entropy and their backward
with the head's second forward under the exit's checkpoint (scopes
``exit_<t>``, part ``early_exits``), per step run on chip 0: what training
every exit costs over training the last, whose time is ``step.head_ms``.
None where the program has no such scope."""
import program_trace


def read(facts):
    return program_trace.part_ms(facts, "early_exits")
