"""The scalar-decay delta rule's share of its roofline: the least time the
chip could take for the recurrences of one step, max(operations ÷ peak
FLOP/s, bytes ÷ peak bytes/s) with the builder's ``gdn_flops_per_step`` (a
token's state read at ``k``, rank-one update, state read at ``q`` and
decay; backward twice the forward) and ``gdn_bytes_per_step`` (``q``,
``k``, ``v``, ``g``, ``beta``, ``o`` and their gradients, once each way),
over the device time under the ``scan`` scope (part ``gdn_scan``). Both
count what no algorithm can avoid, so the share cannot pass 100% and reads
the same work whatever implements the scan."""
import program_trace


def read(facts):
    ms, peaks = program_trace.part_ms(facts, "gdn_scan"), facts.get("peaks")
    model = facts["cell"].model
    flops_of = getattr(model, "gdn_flops_per_step", None)
    bytes_of = getattr(model, "gdn_bytes_per_step", None)
    if not ms or not peaks or flops_of is None or bytes_of is None:
        return None
    args = (facts["cell"].sizes, facts["cell"].traffic,
            facts["per_chip_batch"])
    least_s = max(flops_of(*args) / peaks["bf16_flops"],
                  bytes_of(*args) / peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms * 1e-3)
