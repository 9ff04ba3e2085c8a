"""Device time of one train step spent selecting: every query's threshold
(the ``topk``-th largest of its causal scores, by a bisection over the
floats' bits), the selection's logsumexp and size (scope ``attn/select``),
per step run on chip 0. It has no backward: the selection is kept. None
where the program has no such scope."""
import sparse_parts


def read(facts):
    return sparse_parts.parts_ms(facts).get("select")
