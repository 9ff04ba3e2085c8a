"""Share of the traced window the main thread spent inside a ``df/action`` or
``df/from_pandas`` span and outside every ``df/stage`` span: the driver's
own work in the DataFrame calls."""
import program_trace


def read(facts):
    return program_trace.summary(facts).get("driver_share")
