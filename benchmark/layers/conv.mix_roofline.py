"""The gated short convolution's share of its roofline, which is bytes:
the builder's ``conv_bytes_per_step`` (``B``, ``C``, ``x`` read and the
gated output written once forward, those and their gradients once
backward) over the chip's peak bytes/s, over the device time under the
``conv/conv`` scope (part ``conv_mix``: both gates and the 3-tap causal
depthwise convolution, forward, the checkpointed forward again, and
backward). The bytes are what no algorithm can avoid, so the share cannot
pass 100%."""
import program_trace


def read(facts):
    ms, peaks = program_trace.part_ms(facts, "conv_mix"), facts.get("peaks")
    cell = facts["cell"]
    bytes_of = getattr(cell.model, "conv_bytes_per_step", None)
    if not ms or not peaks or bytes_of is None:
        return None
    moved = bytes_of(cell.sizes, cell.traffic, facts["per_chip_batch"])
    return 100.0 * moved / peaks["hbm_bytes_per_s"] / (ms * 1e-3)
