"""The loader's wait for the lazily run last ETL stage a traced job: time inside
``handoff/await_blocks`` (``MLDataset._ensure_plan`` resolving its pending
blocks), summed over the profile's window and divided by the ``train/fit``
spans that close in it, in ms. ETL work seen from the loader: only overlap
or a shorter last stage lowers it."""
import body_trace


def read(facts):
    return body_trace.summary(facts).get("await_ms")
