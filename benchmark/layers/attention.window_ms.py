"""What the window layers' attention costs: the part of
``step.attention_ms`` under the sliding layers' attention modules (scope
``attn_window``: projections, rotation, kernels, the gate a head, the
output projection), forward and backward, per step run on chip 0. The
part rules give attention as a whole; this is a second reduction of the
same profile, as ``attention.kernel_roofline``'s. None where the program
has no such scope."""
import glob
import os

import program_trace

WINDOW = [[r"/attn_window(/|$)", "window"]]


def read(facts):
    cell = facts["cell"]
    paths = sorted(glob.glob(os.path.join(
        os.path.dirname(cell.bench_dir), "benchmark_out", "trace",
        "plugins", "profile", "*", "*.xplane.pb",
    )))
    if not paths:
        return None
    summary, _ = program_trace.reduce_profile(
        program_trace.load_profile(paths[-1]), WINDOW
    )
    return summary.get("parts_ms", {}).get("window") or None
