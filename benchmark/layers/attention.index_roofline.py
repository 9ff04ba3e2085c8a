"""The index scores as a share of their roofline: one product of ``Hi·Di``
for every CAUSAL pair, forward (the builder's
``index_score_flops_per_step``: there is no ranking without them), over the
chip's peak, over the device time of the kernel that makes them
(``attn/index/sparse_index_scores``). Their backward runs over the selected
pairs inside attention's backward kernels and is counted, operations and
time, in ``attention.sparse_roofline``. None where the program has no such
scope or the builder no such count."""
import sparse_parts


def read(facts):
    return sparse_parts.roofline(
        facts, "scores", "index_score_flops_per_step"
    )
