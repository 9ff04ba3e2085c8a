"""Device time of one train step that no rule of ``parts/<builder>.json`` names:
final norm, head, loss, collectives, the compiler's own copies. Large means
the rules or the program's scopes have a hole."""
import program_trace


def read(facts):
    return program_trace.part_ms(facts, "rest")
