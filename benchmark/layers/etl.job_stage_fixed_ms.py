"""The fixed cost of a cluster stage: the median, over the ``cluster`` records
the engine's ``stage_store`` retains (its newest 512: the window's last jobs),
of a stage's wall less the time its critical envelope spent in task bodies
(``wall_s - exec_s``), in ms. What is left when the work is taken out:
submission, transit, loading the functions, the driver's own time."""
import stage_trace


def read(facts):
    return stage_trace.summary(facts).get("stage_fixed_ms")
