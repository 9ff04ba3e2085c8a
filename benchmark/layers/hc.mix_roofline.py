"""The stream mixings' share of their roofline, which is bytes: the
builder's ``hc_bytes_per_step`` (forward, ``pre`` reads the n streams and
writes the sublayer's input, ``post`` reads the n streams and the
sublayer's output and writes the n streams; those and their gradients once
backward) over the chip's peak bytes/s, over the device time under the
``hc_attn`` and ``hc_ffn`` scopes' ``pre`` and ``post`` (part ``hc_mix``:
forward, the checkpointed forward again, and backward). The bytes are what
no algorithm can avoid, so the share cannot pass 100%."""
import program_trace


def read(facts):
    ms, peaks = program_trace.part_ms(facts, "hc_mix"), facts.get("peaks")
    cell = facts["cell"]
    bytes_of = getattr(cell.model, "hc_bytes_per_step", None)
    if not ms or not peaks or bytes_of is None:
        return None
    moved = bytes_of(cell.sizes, cell.traffic, facts["per_chip_batch"])
    return 100.0 * moved / peaks["hbm_bytes_per_s"] / (ms * 1e-3)
