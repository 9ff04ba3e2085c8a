"""The rate the expert exchange achieved: the bytes one chip sends plus
receives in the routed layers' collectives in a step, from the shapes (the
builder's ``exchange_bytes_per_step``), over the device time of those
collectives on chip 0 (``moe.exchange_ms``). That time includes the wait
for the fullest chip inside the reduce-scatter, so this is the rate at
which the exchange got its bytes through the step, a LOWER bound on what
the links carried, and it rises as the chips' loads even out with no byte
changed (102.6 unplaced, 185.6 placed: PERF.md section 6, PR 53). Before
it can be read against the chip's inter-chip peak (not in ``peaks.json``
yet) the wait has to be taken out of the time. None where the program has
no exchange or the builder no such count."""
import os

import harness


def read(facts):
    cell = facts["cell"]
    bytes_of = getattr(cell.model, "exchange_bytes_per_step", None)
    if bytes_of is None:
        return None
    ms = harness.load_module(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "moe.exchange_ms.py"
    )).read(facts)
    if not ms:
        return None
    moved = bytes_of(cell.sizes, cell.traffic, facts["per_chip_batch"])
    return moved / (ms * 1e-3) / 1e9
