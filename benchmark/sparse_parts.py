"""A second reduction of a traced run's profile by the scopes of a "sparse"
attention layer (``models/sparse_index.py``): what the four
``attention.{index,select}_ms`` / ``attention.{index,sparse}_roofline``
readers share. The part rules of a configuration give attention as a whole
(``step.attention_ms``); these split it, forward and backward, per step run
on chip 0:

* ``scores``: the kernel that scores every causal pair
  (``attn/index/sparse_index_scores``);
* ``index``: the rest of the index branch (its three projections, the
  norm, the rotation; scope ``attn/index``);
* ``index_loss``: the index loss outside the kernels (``attn/index_loss``);
* ``select``: the thresholds (``attn/select``);
* ``sparse``: attention over the selection, its kernels and the layout
  moves around them (``attn/sparse``).

``{}`` where the run has no profile; a part is absent where the program has
no such scope (every program before the mixer, every other model).
"""
import glob
import os

import program_trace

RULES = [
    [r"/attn/(\S*/)?index/sparse_index_scores(/|$)", "scores"],
    [r"/attn/(\S*/)?index(/|$)", "index"],
    [r"/attn/(\S*/)?index_loss(/|$)", "index_loss"],
    [r"/attn/(\S*/)?select(/|$)", "select"],
    [r"/attn/(\S*/)?sparse(/|$)", "sparse"],
]
_CACHE: dict = {}


def parts_ms(facts) -> dict:
    cell = facts["cell"]
    paths = sorted(glob.glob(os.path.join(
        os.path.dirname(cell.bench_dir), "benchmark_out", "trace",
        "plugins", "profile", "*", "*.xplane.pb",
    )))
    if not paths:
        return {}
    key = (paths[-1], os.path.getmtime(paths[-1]))
    if key not in _CACHE:
        summary, _ = program_trace.reduce_profile(
            program_trace.load_profile(paths[-1]), RULES
        )
        _CACHE.clear()
        _CACHE[key] = {
            part: ms for part, ms in summary.get("parts_ms", {}).items()
            if part != "rest" and ms
        }
    return _CACHE[key]


def roofline(facts, part: str, count: str):
    """The operations the builder's ``count`` gives for one step over the
    chip's peak over ``part``'s device time, in percent; None where the
    program has no such part or the builder no such count."""
    cell, peaks = facts["cell"], facts.get("peaks")
    flops_of = getattr(cell.model, count, None)
    ms = parts_ms(facts).get(part)
    if not ms or not peaks or flops_of is None:
        return None
    flops = flops_of(cell.sizes, cell.traffic, facts["per_chip_batch"])
    return 100.0 * flops / peaks["bf16_flops"] / (ms * 1e-3)
