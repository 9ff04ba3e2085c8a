"""Pairs on the fullest chip's experts over the mean chip's, over the last
epoch and all routed layers: the program's gauge
``moe/chip_load_max_over_mean``, set from counts summed on the device and
fetched with the epoch's loss. The step waits for the fullest chip of the
experts' axis; 1.0 is perfect balance, and no pair is dropped whatever it
reads. None where the experts lie on one chip."""


def read(facts):
    from raydp_tpu.utils.profiling import metrics

    return metrics.gauge_value("moe/chip_load_max_over_mean") or None
