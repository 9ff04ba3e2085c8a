"""The grouped-matmul kernels' share of their roofline: the operations of
the experts' products for the ``T·k`` rows that exist, forward and backward
(the builder's ``moe_flops_per_step``), over the chip's peak, over the
device time of the kernels themselves (part ``moe_gmm``: the ``gmm`` and
``tgmm`` Pallas calls under ``moe/experts``). Bound by operations: at 1,024
rows an expert the weights' bytes are a tenth of that time."""
import program_trace


def read(facts):
    ms, peaks = program_trace.part_ms(facts, "moe_gmm"), facts.get("peaks")
    cell = facts["cell"]
    flops_of = getattr(cell.model, "moe_flops_per_step", None)
    if not ms or not peaks or flops_of is None:
        return None
    flops = flops_of(cell.sizes, cell.traffic, facts["per_chip_batch"])
    return 100.0 * flops / peaks["bf16_flops"] / (ms * 1e-3)
