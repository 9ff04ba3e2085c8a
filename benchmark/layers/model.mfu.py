"""Model FLOP/s utilisation: operations the forward and backward passes
need per sample (the configuration's ``flops_per_sample``) x samples/s of
the traced epochs, over chips x peak."""


def read(facts):
    rate, peaks = facts.get("traced_samples_per_s"), facts.get("peaks")
    if not rate or not peaks:
        return None
    cell = facts["cell"]
    flops = cell.model.flops_per_sample(cell.sizes, cell.traffic)
    return 100.0 * flops * rate / (facts["chips"] * peaks["bf16_flops"])
