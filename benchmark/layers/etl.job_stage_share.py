"""Share of the jobs' wall time in which the ETL engine ran a stage, by its
own records (``stage_store``: the driver-side wall of every stage a job
started, from its start to its last task), the lazily run final stage
included. That stage overlaps the epoch's first steps, and the driver's
own work between stages (collects, thresholds) is not in it: what an
engine change can shorten, not the ETL's share of the critical path."""


def read(facts):
    if not facts.get("base_s") or "etl_stage_s" not in facts:
        return None
    return 100.0 * facts["etl_stage_s"] / facts["base_s"]
