"""Device time of one train step inside the Gated DeltaNet layers' mixers,
forward and backward: the projections and the layer's output norm (part
``gdn_proj``), the convolutions, L2 norms, decay, beta and the gated norm
(``gdn_conv_gate``) and the delta rule (``gdn_scan``), per step run on
chip 0. None where the program has no such scopes."""
import program_trace


def read(facts):
    parts = program_trace.summary(facts).get("parts_ms", {})
    mine = [v for k, v in parts.items() if k.startswith("gdn_")]
    return sum(mine) if mine else None
