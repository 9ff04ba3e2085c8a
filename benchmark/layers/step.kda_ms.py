"""Device time of one train step inside the Kimi Delta Attention layers'
mixers, forward and backward: the projections, both low-rank pairs and
the layer's norm (part ``kda_proj``), the convolutions, L2 norms, decay,
beta and the gated norm (``kda_conv_gate``) and the delta rule
(``kda_scan``), per step run on chip 0. None where the program has no
such scopes."""
import program_trace


def read(facts):
    parts = program_trace.summary(facts).get("parts_ms", {})
    mine = [v for k, v in parts.items() if k.startswith("kda_")]
    return sum(mine) if mine else None
