"""The window layers' flash kernels' share of their roofline: the
operations of attention over the pairs INSIDE the window, ``S W - W(W-1)/2``
a head, forward and the blockwise backward (the builder's
``window_attention_flops_per_step``), over the chip's peak, over the
device time of the kernels themselves (the Pallas calls under
``attn_window/jit(flash_attention)``). The pairs are what no algorithm can
avoid, so the share cannot pass 100%, and a kernel that masks what it
could skip reads low. ``attention.kernel_roofline`` reads the calls under
``attn``, the layers over all positions, alone. None where the program
has no such scope or the builder no such count."""
import glob
import os

import program_trace

KERNELS = [[r"/attn_window/jit\(flash_attention\)/pallas_call", "kernel"]]


def read(facts):
    cell, peaks = facts["cell"], facts.get("peaks")
    flops_of = getattr(cell.model, "window_attention_flops_per_step", None)
    paths = sorted(glob.glob(os.path.join(
        os.path.dirname(cell.bench_dir), "benchmark_out", "trace",
        "plugins", "profile", "*", "*.xplane.pb",
    )))
    if not paths or not peaks or flops_of is None:
        return None
    summary, _ = program_trace.reduce_profile(
        program_trace.load_profile(paths[-1]), KERNELS
    )
    ms = summary.get("parts_ms", {}).get("kernel")
    if not ms:
        return None
    flops = flops_of(cell.sizes, cell.traffic, facts["per_chip_batch"])
    return 100.0 * flops / peaks["bf16_flops"] / (ms * 1e-3)
