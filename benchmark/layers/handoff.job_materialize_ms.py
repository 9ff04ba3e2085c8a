"""The hand-off's OWN work a traced job: time inside ``handoff/fetch`` (the
shard's blocks resolved from the store) and ``handoff/convert``
(``concat_tables`` and ``to_numpy``) on the loader's producer thread, summed
over the profile's window and divided by the ``train/fit`` spans that close in
it, in ms. The wait for the last ETL stage is not in it
(``handoff.job_await_ms``)."""
import body_trace


def read(facts):
    return body_trace.summary(facts).get("materialize_ms")
