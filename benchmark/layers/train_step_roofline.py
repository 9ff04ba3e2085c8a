"""The step's share of its roofline: the least time one chip could take
for its part of a step, max(required FLOPs / peak FLOP/s, required bytes /
peak bytes/s), over the device time of a step. Which of the two bounds it
goes to the notes as ``roofline_bound_by``."""


def read(facts):
    step_ms = (facts.get("trace") or {}).get("step_device_ms")
    peaks = facts.get("peaks")
    if not step_ms or not peaks:
        return None
    cell, batch = facts["cell"], facts["per_chip_batch"]
    by_flops = batch * cell.model.flops_per_sample(
        cell.sizes, cell.traffic
    ) / peaks["bf16_flops"]
    by_bytes = cell.model.bytes_per_step(
        cell.sizes, cell.traffic, batch
    ) / peaks["hbm_bytes_per_s"]
    facts["roofline_bound_by"] = {
        "bound": "flops" if by_flops >= by_bytes else "bytes",
        "flops_ms": by_flops * 1e3, "bytes_ms": by_bytes * 1e3,
    }
    return 100.0 * max(by_flops, by_bytes) * 1e3 / step_ms
