"""Device time of one train step in a sparse layer's index branch outside
attention's own kernels: its three projections, the norm and the rotation,
the kernel that scores every causal pair, and the index loss (scopes
``attn/index`` and ``attn/index_loss``), forward and backward, per step run
on chip 0. The branch's backward THROUGH the scores runs inside attention's
backward kernels, which hold the probabilities it learns from, and is in
``attention.sparse_roofline``'s time. None where the program has no such
scope."""
import sparse_parts


def read(facts):
    parts = sparse_parts.parts_ms(facts)
    mine = [parts[p] for p in ("scores", "index", "index_loss") if p in parts]
    return sum(mine) if mine else None
