"""The part of ``step.moe_ms`` that moves rows: the sorts, the gather into
expert order and the gather back with its weighted sum over k, forward and
backward (scopes ``moe/permute`` and ``moe/unpermute``, part
``moe_permute``), per step run on chip 0. Were either direction a scatter
of ``T·k`` rows it would show here (PERF.md §6, PR 25: 95 ns a row)."""
import program_trace


def read(facts):
    return program_trace.part_ms(facts, "moe_permute")
