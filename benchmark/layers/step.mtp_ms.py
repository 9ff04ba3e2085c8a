"""Device time of one train step in the multi-token-prediction module,
forward and backward: its block (part ``mtp_block``), its two norms, the
next token's lookup, its projection and its closing norm (``mtp_glue``),
and the second pass over the vocabulary with its cross-entropy
(``mtp_head``), per step run on chip 0: what the objective costs. None
where the program has no such scopes."""
import program_trace


def read(facts):
    parts = program_trace.summary(facts).get("parts_ms", {})
    mine = [v for k, v in parts.items() if k.startswith("mtp_")]
    return sum(mine) or None
