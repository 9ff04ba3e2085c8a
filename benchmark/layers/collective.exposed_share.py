"""Share of the traced window chip 0 spent in collective operations while
no other operation ran there (profiler trace). None on one chip."""


def read(facts):
    trace = facts.get("trace") or {}
    if not trace or trace.get("chips", 1) < 2:
        return None
    return 100.0 * trace["exposed_collective_s"] / trace["window_s"]
