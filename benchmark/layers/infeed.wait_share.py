"""Share of the measured time the step loop sat blocked on the loader's
queue: the program's ``ingest/wait_seconds`` counter over the window (fit
cells) or over the jobs' wall time. In a job whose hand-off streams
(``from_df`` hands over pending futures) the loader's blocks are ETL tasks
still running, so the same reading is the wait for the last ETL stage:
``handoff.job_wait_share``."""


def read(facts):
    if not facts.get("base_s"):
        return None
    return 100.0 * facts["infeed_wait_s"] / facts["base_s"]
