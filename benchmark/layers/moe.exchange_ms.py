"""What the routed layers' expert exchange costs: the part of
``step.moe_ms`` under the exchange's scopes (``moe/.../exchange/gather``:
the all-gather of a layer's tokens with their gates and choices;
``moe/.../exchange/scatter``: the reduce-scatter of the parts; and their
transposes in the backward pass), per step run on chip 0. Device time of
the collectives' own operations, whether or not other work hid it
(``collective.exposed_share`` says what was not hidden). That time holds
the WAIT for the other chips as well as the transfer: a reduce-scatter
ends when the fullest chip's experts have finished, so the reading falls
with ``moe.chip_load_max_over_mean`` at the same bytes (26.7 ms unplaced,
14.8 placed: PERF.md section 6, PR 53) and is the exchange's cost in the
step, not the links' time. A second
reduction of the same profile, as ``attention.window_ms``'s, kept in
``facts`` for ``moe.exchange_gb_per_s``. None where the program has no such
scope."""
import glob
import os

import program_trace

EXCHANGE = [[r"/moe/(shard_map/)?exchange(/|$)", "exchange"]]


def read(facts):
    if "moe_exchange_ms" in facts:
        return facts["moe_exchange_ms"]
    cell = facts["cell"]
    paths = sorted(glob.glob(os.path.join(
        os.path.dirname(cell.bench_dir), "benchmark_out", "trace",
        "plugins", "profile", "*", "*.xplane.pb",
    )))
    if not paths:
        return None
    summary, _ = program_trace.reduce_profile(
        program_trace.load_profile(paths[-1]), EXCHANGE
    )
    facts["moe_exchange_ms"] = summary.get("parts_ms", {}).get(
        "exchange"
    ) or None
    return facts["moe_exchange_ms"]
