"""Device time of one train step inside the state-space layers' mixers,
forward and backward: the in- and out-projections and the layer's norm
(part ``ssm_proj``), the convolution and the gated norm (``ssm_conv_gate``)
and the scan (``ssm_ssd``), per step run on chip 0. None where the program
has no such scopes."""
import program_trace


def read(facts):
    parts = program_trace.summary(facts).get("parts_ms", {})
    mine = [v for k, v in parts.items() if k.startswith("ssm_")]
    return sum(mine) if mine else None
