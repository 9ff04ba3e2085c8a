"""Device time of one train step: the union of the device-op intervals on
chip 0 inside the runs of the step's program, per run (profiler trace)."""


def read(facts):
    return (facts.get("trace") or {}).get("step_device_ms")
