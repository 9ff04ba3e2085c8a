"""Share of the jobs' wall time spent in the job's DataFrame calls:
``from_pandas`` of the raw chunk, the groupBy-and-count actions with their
collects to the driver, and the PLAN of the final stage. That stage runs
later, under the loader (``handoff.job_wait_share``), so this is the ETL
in front of the epoch and not all of it. The benchmark's own host clock
around the calls, summed over the window's jobs."""


def read(facts):
    if not facts.get("base_s") or "etl_calls_s" not in facts:
        return None
    return 100.0 * facts["etl_calls_s"] / facts["base_s"]
