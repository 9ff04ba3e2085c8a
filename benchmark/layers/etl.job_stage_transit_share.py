"""Share of the cluster stages' wall between the driver's send and the
worker's handler, and back: ``(reply - send) - (ret - recv)`` of each
round's critical envelope — the request's pickle, gRPC both ways, the
reply's pickle. Needs no clock in common."""
import stage_trace


def read(facts):
    return stage_trace.summary(facts).get("transit_share")
