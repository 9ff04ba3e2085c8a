"""The module's loss over the main model's, over the last epoch: the
program's gauges ``train/loss_mtp`` and ``train/loss_main``, the means of
the two losses a step (summed on the device and fetched with the epoch's
loss). Near 1 at random weights, a little over 1 in training (two tokens
ahead is the harder target); 0, not a number or absent says the module is
not in the step. None where the program sets no such gauges."""
import math


def read(facts):
    from raydp_tpu.utils.profiling import metrics

    main = metrics.gauge_value("train/loss_main")
    module = metrics.gauge_value("train/loss_mtp")
    if not main or module is None or not math.isfinite(main):
        return None
    return module / main
