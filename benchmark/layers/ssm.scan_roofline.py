"""The state-space scan's share of its roofline: the least time the chip
could take for the scans of one step, max(operations ÷ peak FLOP/s, bytes
÷ peak bytes/s) with the builder's ``ssd_flops_per_step`` (causal pairs
inside a chunk, the chunk state, the carried-in part; backward twice the
forward) and ``ssd_bytes_per_step`` (``x``, ``B``, ``C``, ``dt``, ``y`` and
their gradients, once each way), over the device time under the ``ssd``
scope (part ``ssm_ssd``). Both count what no algorithm can avoid, so the
share cannot pass 100%; it is bound by bytes at the published widths."""
import program_trace


def read(facts):
    ms, peaks = program_trace.part_ms(facts, "ssm_ssd"), facts.get("peaks")
    model = facts["cell"].model
    flops_of = getattr(model, "ssd_flops_per_step", None)
    bytes_of = getattr(model, "ssd_bytes_per_step", None)
    if not ms or not peaks or flops_of is None or bytes_of is None:
        return None
    args = (facts["cell"].sizes, facts["cell"].traffic,
            facts["per_chip_batch"])
    least_s = max(flops_of(*args) / peaks["bf16_flops"],
                  bytes_of(*args) / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms * 1e-3)
