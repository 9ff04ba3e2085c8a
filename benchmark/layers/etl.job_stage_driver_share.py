"""Share of the cluster stages' wall that is the driver's: submission
(``submit_s``: staging, grouping, a thread per worker, a pickle per
envelope) and its self time (``driver_s``: before the first round, between
rounds, after the last reply; for a streaming stage the wait for upstream
partitions too)."""
import stage_trace


def read(facts):
    return stage_trace.summary(facts).get("driver_share")
