"""The part of ``step.kda_ms`` that is not a projection: the three 4-tap
causal convolutions with their SiLU and L2 norms, the decay and beta, the
chunked delta rule and the gated norm a head (scopes ``kda/conv``,
``kda/decay``, ``kda/beta``, ``kda/scan``, ``kda/gate_norm``; parts
``kda_conv_gate`` and ``kda_scan``), forward and backward, per step run on
chip 0."""
import program_trace


def read(facts):
    parts = program_trace.summary(facts).get("parts_ms", {})
    mine = [parts[p] for p in ("kda_scan", "kda_conv_gate") if p in parts]
    return sum(mine) if mine else None
