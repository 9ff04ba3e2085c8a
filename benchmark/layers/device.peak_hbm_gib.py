"""Peak HBM of the fullest chip after the window, live arrays plus the
loaded programs' temporaries (``harness.memory_peak_bytes``)."""


def read(facts):
    peak = facts.get("memory_peak_bytes")
    return peak / 2 ** 30 if peak else None
