"""Device time of one train step on the residual path's streams, forward
and backward: each sublayer's three mappings with their Sinkhorn rounds
(part ``hc_maps``), the two mixings that read the streams for a sublayer
and write its output back to them (``hc_mix``), and the streams' start and
end (``hc_ends``), per step run on chip 0. None where the program has no
such scopes."""
import program_trace


def read(facts):
    parts = program_trace.summary(facts).get("parts_ms", {})
    mine = [v for k, v in parts.items() if k.startswith("hc_")]
    return sum(mine) if mine else None
