"""Share of the traced jobs' ``ingest/wait`` time (the loader waiting for
the lazily run last ETL stage) during which at least one worker ran a task
body, the bodies placed on the profile's clock by ``stage_trace``. Near 100:
the wait is ETL work and only overlap shortens it; low: it is fixed cost."""
import stage_trace


def read(facts):
    return stage_trace.summary(facts).get("wait_worker_busy_share")
