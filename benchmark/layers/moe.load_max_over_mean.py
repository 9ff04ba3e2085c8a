"""Tokens of the fullest expert over the mean expert's, over the last epoch
and all routed layers: the program's gauge ``moe/load_max_over_mean``, set
from expert counts summed on the device and fetched with the epoch's loss.
1.0 is perfect balance; no token is dropped whatever it reads."""


def read(facts):
    from raydp_tpu.utils.profiling import metrics

    return metrics.gauge_value("moe/load_max_over_mean")
