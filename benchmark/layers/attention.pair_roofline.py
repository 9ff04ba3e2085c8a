"""Attention under block diffusion's pair mask as a share of its roofline:
the operations of attention over the pairs INSIDE the mask, ``S² + S·L`` a
head, forward and the blockwise backward (the builder's
``attention_flops_per_step``), over the chip's peak, over the device time
of EVERYTHING between the rotated q, k, v and the attention's output: the
Pallas calls and the layout moves around them
(``attn/jit(flash_attention)``), and what the program does beside them
under ``attn/pair`` (the noised copy's own-block term, the merge through
the rows' logsumexp, the two halves put together). So it reads the same
work whatever implements the mask, where ``attention.kernel_roofline``
reads the Pallas calls alone. The pairs are what no algorithm can avoid,
so the share cannot pass 100%. None where the program has no such scope
or the builder no such count."""
import glob
import os

import program_trace

PAIR = [[r"/attn/(pair|jit\(flash_attention\))(/|$)", "pair"]]


def read(facts):
    cell, peaks = facts["cell"], facts.get("peaks")
    flops_of = getattr(cell.model, "attention_flops_per_step", None)
    if getattr(cell.model, "mask_pairs", None) is None:
        return None
    paths = sorted(glob.glob(os.path.join(
        os.path.dirname(cell.bench_dir), "benchmark_out", "trace",
        "plugins", "profile", "*", "*.xplane.pb",
    )))
    if not paths or not peaks or flops_of is None:
        return None
    summary, _ = program_trace.reduce_profile(
        program_trace.load_profile(paths[-1]), PAIR
    )
    ms = summary.get("parts_ms", {}).get("pair")
    if not ms:
        return None
    flops = flops_of(cell.sizes, cell.traffic, facts["per_chip_batch"])
    return 100.0 * flops / peaks["bf16_flops"] / (ms * 1e-3)
