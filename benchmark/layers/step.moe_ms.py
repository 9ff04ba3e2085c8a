"""Device time of one train step inside the routed layers, forward and
backward: router, permute, the experts (grouped matmuls, SwiGLU, the
weights' casts), un-permute and the layer's norm (parts ``moe_permute``,
``moe_gmm``, ``moe_rest``), per step run on chip 0."""
import program_trace


def read(facts):
    parts = program_trace.summary(facts).get("parts_ms", {})
    mine = [v for k, v in parts.items() if k.startswith("moe_")]
    return sum(mine) if mine else None
