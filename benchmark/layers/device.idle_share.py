"""Share of the traced window in which no operation ran on the device,
averaged over the chips: 1 - busy / window (profiler trace)."""


def read(facts):
    trace = facts.get("trace") or {}
    if not trace:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
