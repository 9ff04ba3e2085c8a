"""What a cluster task's body spends WRITING its output to the object store
(``store.put_arrow_table``, without the RPC that follows): the median, over
the retained ``cluster`` records with stamped bodies, of ``put_s /
tasks_stamped`` in ms."""
import body_trace


def read(facts):
    return body_trace.summary(facts).get("task_put_ms")
