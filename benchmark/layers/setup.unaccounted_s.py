"""``setup.ready_s`` less the time under the program's retained ``cluster/start``,
``mesh/build`` and ``train/fit`` spans up to the ready stamp: what no start-up
phase of the program covers (imports, the caller's client and data)."""
import startup_trace


def read(facts):
    return startup_trace.summary(facts).get("unaccounted_s")
