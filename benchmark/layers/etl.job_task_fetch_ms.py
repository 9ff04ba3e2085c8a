"""What a cluster task's body spends FETCHING its inputs: the median, over the
``cluster`` records the engine's ``stage_store`` retains whose workers stamp
their bodies, of ``fetch_s / tasks_stamped`` in ms — the time inside
``WorkerContext.get_table`` (a counting stage has one task: its body's own
eight fetches). Absolute, not a share of the body."""
import body_trace


def read(facts):
    return body_trace.summary(facts).get("task_fetch_ms")
