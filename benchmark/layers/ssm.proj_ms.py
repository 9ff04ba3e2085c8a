"""The part of ``step.ssm_ms`` that the matrix unit does: the state-space
layers' norm and their in- and out-projections (scopes ``ln_mamba``,
``mamba/in_proj``, ``mamba/out_proj``; part ``ssm_proj``), forward and
backward, per step run on chip 0. ``ssm.scan_ms`` is the rest of the
mixer. None where the program has no such scopes."""
import program_trace


def read(facts):
    return program_trace.part_ms(facts, "ssm_proj") or None
