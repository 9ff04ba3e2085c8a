"""Share of the cluster stages' wall (the retained ``stage_store`` records,
summed) during which the critical envelope's task bodies ran: the work
itself. The four ``etl.job_stage_*_share`` metrics sum to 100."""
import stage_trace


def read(facts):
    return stage_trace.summary(facts).get("exec_share")
