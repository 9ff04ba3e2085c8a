"""Share of the traced jobs' window in which chip 0 ran nothing AND a
``handoff/fetch`` or ``handoff/convert`` span was open: the idle chip put
down to the hand-off's own work."""
import body_trace


def read(facts):
    return body_trace.summary(facts).get("handoff_idle_share")
