"""What latent attention costs outside its kernels: the part of
``step.attention_ms`` under the five projections and the two latent norms
(scopes ``attn/{q_down, q_up, kv_down, kv_up, out, q_norm, kv_norm}``),
forward and backward, per step run on chip 0. The part rules give
attention as a whole; this is a second reduction of the same profile, as
``attention.kernel_roofline``'s. None where the program has no such
scopes."""
import glob
import os

import program_trace

PROJECTIONS = [[
    r"/attn/(q_down|q_up|kv_down|kv_up|out|q_norm|kv_norm)(/|$)", "proj"
]]


def read(facts):
    cell = facts["cell"]
    paths = sorted(glob.glob(os.path.join(
        os.path.dirname(cell.bench_dir), "benchmark_out", "trace",
        "plugins", "profile", "*", "*.xplane.pb",
    )))
    if not paths:
        return None
    summary, _ = program_trace.reduce_profile(
        program_trace.load_profile(paths[-1]), PROJECTIONS
    )
    return summary.get("parts_ms", {}).get("proj") or None
