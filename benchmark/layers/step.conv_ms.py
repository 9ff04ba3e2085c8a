"""Device time of one train step inside the short-convolution operators,
forward and backward: the in- and out-projections and the layer's norm
(part ``conv_proj``) and the two gates with the causal depthwise
convolution between them (``conv_mix``), per step run on chip 0. None
where the program has no such scopes."""
import program_trace


def read(facts):
    parts = program_trace.summary(facts).get("parts_ms", {})
    mine = [v for k, v in parts.items() if k.startswith("conv_")]
    return sum(mine) if mine else None
