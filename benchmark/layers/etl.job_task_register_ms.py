"""What a cluster task's body spends REGISTERING its output with the master (the
``RegisterObject`` round trip it waits for): the median, over the retained
``cluster`` records with stamped bodies, of ``register_s / tasks_stamped`` in
ms."""
import body_trace


def read(facts):
    return body_trace.summary(facts).get("task_register_ms")
