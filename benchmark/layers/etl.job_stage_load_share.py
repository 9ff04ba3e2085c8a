"""Share of the cluster stages' wall the critical envelope's worker spent
outside task bodies: unpickling the functions, resolving data refs, its
task pool's hand-over (``(ret - recv)`` less the union of the bodies)."""
import stage_trace


def read(facts):
    return stage_trace.summary(facts).get("load_share")
