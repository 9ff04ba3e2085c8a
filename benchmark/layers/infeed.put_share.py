"""Share of the traced window the step loop's thread spent inside the program's
``infeed/put`` and ``ingest/device_put`` spans: the host's put calls, not
the transfer."""
import program_trace


def read(facts):
    return program_trace.summary(facts).get("put_share")
