"""Attention over a learned selection as a share of its roofline: the
operations of attention over the SELECTED pairs, forward and the blockwise
backward, and of the index branch's backward over the same pairs (the
builder's ``sparse_attention_flops_per_step``), over the chip's peak, over
the device time of EVERYTHING under ``attn/sparse``: the kernels (which
rebuild a tile's mask from the index branch and walk the scores a second
time for the heads' mean probability) and the layout moves around them. The
selected pairs are what no algorithm can avoid, so kernels that compute
every causal pair under the mask read low and the share cannot pass 100%
whatever implements the mask. None where the program has no such scope or
the builder no such count."""
import sparse_parts


def read(facts):
    return sparse_parts.roofline(
        facts, "sparse", "sparse_attention_flops_per_step"
    )
