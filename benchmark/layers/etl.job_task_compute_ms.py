"""What a cluster task's body spends in its own kernel (concatenate, aggregate,
the mapped function): the median, over the retained ``cluster`` records with
stamped bodies, of ``(body_s - fetch_s - put_s - register_s) / tasks_stamped``
in ms. Never stamped: the body less the parts a worker stamps."""
import body_trace


def read(facts):
    return body_trace.summary(facts).get("task_compute_ms")
