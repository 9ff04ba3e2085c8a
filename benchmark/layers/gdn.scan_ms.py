"""The part of ``step.gdn_ms`` that is not a projection: the three 4-tap
causal convolutions with their SiLU and L2 norms, the decay a head and
beta, the chunked delta rule and the gated norm a head (scopes
``gdn/conv``, ``gdn/decay``, ``gdn/beta``, ``gdn/scan``,
``gdn/gate_norm``; parts ``gdn_conv_gate`` and ``gdn_scan``), forward and
backward, per step run on chip 0."""
import program_trace


def read(facts):
    parts = program_trace.summary(facts).get("parts_ms", {})
    mine = [parts[p] for p in ("gdn_scan", "gdn_conv_gate") if p in parts]
    return sum(mine) if mine else None
