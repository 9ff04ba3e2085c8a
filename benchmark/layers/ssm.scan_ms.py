"""The part of ``step.ssm_ms`` that is not a projection: the 4-tap causal
convolution with its SiLU, the chunked scan and the gated norm (scopes
``mamba/conv``, ``mamba/ssd``, ``mamba/gate_norm``; parts ``ssm_conv_gate``
and ``ssm_ssd``), forward and backward, per step run on chip 0."""
import program_trace


def read(facts):
    parts = program_trace.summary(facts).get("parts_ms", {})
    mine = [parts[p] for p in ("ssm_ssd", "ssm_conv_gate") if p in parts]
    return sum(mine) if mine else None
