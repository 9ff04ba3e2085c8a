"""What only a chip can say of a share's token-side sums, in one call:

    chiprun --timeout 1500 -- python scripts/moe_token_sum_on_chip.py \
        [--seed N] [--cells mellum2,sdar] [--forms parent,runs] [--skip-cliff] \
        [--out chiprun_out/moe_token_sum.json]

``out[t] = Σ_j live[t, j] · weight[t, j] · src[inverse[t, j]]`` for a share
that holds ``held`` of ``E`` experts, at the shapes of the cells whose
routed layers are shares (``SHAPES``), each operation ALONE, bf16 rows and
float32 gates, three ways:

* ``parent``: ``models/moe.py`` without ``runs``: ``src[inverse]`` gathers
  all ``T·k`` rows, masks the pairs that are not live and sums over ``k``
  (``combine_rows``'s forward with the gates, ``take_rows``'s backward
  without);
* ``gathered`` (the issue's form A): the ``C`` rows brought into token
  order by ONE ``C``-row gather (the live pairs in ascending pair id, a
  sort over ``C`` keys), then every token tile's rows reduced by megablox
  ``tgmm`` with the ``[tq, C]`` selection, its gates as two bfloat16 parts
  one above the other;
* ``runs`` (form B, what the layer runs since PR 54):
  ``ops/rows_to_tokens.py``.

For each: the device's time for a call from a profile (the ``XLA Modules``
line of chip 0, mean over the calls, and the call's dearest operations by
name) and the largest error against the float32 formula over its largest
entry. :func:`cliff` reads the parent's gather over a grid of source sizes
and row widths: why one ``T·k``-row gather is seven times dearer in
Mellum2 than in SDAR (PERF.md §7).

The CPU tests run :func:`draw`, :func:`forms` and :func:`errors` at small
shapes (``tests/test_moe.py``), so the script cannot rot unseen; its times
mean something on a TPU only.
"""
from __future__ import annotations

import argparse
import glob
import importlib
import json
import math
import os
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from raydp_tpu.models import moe  # noqa: E402
from raydp_tpu.ops.rows_to_tokens import rows_to_tokens, tiles  # noqa: E402

# The package's ``gmm`` is the function; ``tgmm`` lives in the module.
megablox = importlib.import_module(
    "jax.experimental.pallas.ops.tpu.megablox.gmm")
_F32 = jnp.float32
# (tokens a step, experts a token, experts routed over, experts held, width)
SHAPES = {
    "mellum2": (16384, 8, 64, 16, 2304),
    "sdar": (16384, 8, 128, 16, 2048),
    "keye": (16384, 8, 128, 16, 2048),
    "laguna": (16384, 8, 256, 32, 2048),
    "kimi": (16384, 8, 256, 8, 2304),
    "lfm2": (8192, 4, 32, 8, 2048),
    "xing4": (4096, 4, 64, 8, 3584),
}


def draw(seed: int, t: int, k: int, e: int, held: int, d: int,
         rows: int = None, dtype=jnp.bfloat16):
    """One layer-step of a share under uniform routing, sorted as
    ``models/moe._sorted_experts`` sorts it: ``src`` ``[C, D]``, ``gate``
    and ``live`` ``[T, k]``, ``order`` ``[C]``, ``inverse`` ``[T·k]`` (cut to
    ``C - 1``) and ``runs`` as ``_experts`` makes them."""
    cfg = moe.MoEConfig(n_experts=e, top_k=k, held_experts=held)
    c = moe.compact_rows(cfg, t) if rows is None else rows
    keys = jax.random.split(jax.random.PRNGKey(seed % (2 ** 31)), 3)
    _, expert = jax.lax.top_k(jax.random.uniform(keys[0], (t, e)), k)
    pairs = jnp.arange(t * k, dtype=jnp.int32)
    key = expert.reshape(-1).astype(jnp.int32)
    live = key < held
    key = jnp.where(live, key, held)
    _, order = jax.lax.sort_key_val(key, pairs)
    _, inverse = jax.lax.sort_key_val(order, pairs)
    live = live.reshape(t, k)
    counts = jnp.sum(jax.nn.one_hot(key, held, dtype=jnp.int32), axis=0)
    ends = jnp.minimum(jnp.cumsum(counts), c)
    return {
        "src": jax.random.normal(keys[1], (c, d), _F32).astype(dtype),
        "gate": jax.random.uniform(keys[2], (t, k), _F32) / k,
        "live": live, "order": order[:c],
        "inverse": jnp.minimum(inverse, c - 1),
        "runs": (jnp.where(live, inverse.reshape(t, k), -1), ends),
    }


def formula(a, weighted: bool):
    """The float32 formula: every pair's row, masked, times its gate."""
    t, k = a["gate"].shape
    pairs = a["src"].astype(_F32)[a["inverse"]].reshape(t, k, -1)
    inside = a["live"] & (a["runs"][0] < a["src"].shape[0])
    weight = jnp.where(inside, a["gate"] if weighted else 1.0, 0.0)
    return jnp.sum(pairs * weight[..., None], axis=1)


def gathered(src, gate, order, runs, tiling=(512, 512, 1024)):
    """Form A. ``gate`` None for ones."""
    place, ends = runs
    t, k = place.shape
    c, d = src.shape
    tq, _ = tiles(t, c, ends.shape[0])
    # The live pairs among the first C of the sorted order, by pair id.
    at = jnp.arange(c, dtype=jnp.int32)
    pair, row = jax.lax.sort_key_val(
        jnp.where(at < ends[-1], order, t * k), at)
    ordered = src[row]                                   # the C-row gather
    is_live = pair < t * k
    pair = jnp.minimum(pair, t * k - 1)
    token = pair // k
    weight = jnp.where(
        is_live, 1.0 if gate is None else gate.reshape(-1)[pair], 0.0)
    hit = (token % tq)[None, :] == jnp.arange(tq, dtype=jnp.int32)[:, None]
    high = weight.astype(src.dtype)
    parts = [high] if gate is None or src.dtype == _F32 else [
        high, (weight - high.astype(_F32)).astype(src.dtype)]
    selection = jnp.concatenate(
        [jnp.where(hit, p[None, :], 0) for p in parts], axis=0)
    sizes = jnp.sum(
        (token // tq)[None, :] == jnp.arange(t // tq)[:, None],
        axis=1, where=is_live[None, :], dtype=jnp.int32)
    tm, tk, tn = tiling
    out = megablox.tgmm(
        selection, ordered, sizes, _F32,
        (math.gcd(c, tm), min(tk, selection.shape[0]), min(tn, d)),
        interpret=jax.default_backend() != "tpu",
    )                                                    # [T/tq, parts·tq, D]
    out = out.reshape(t // tq, len(parts), tq, d).sum(axis=1)
    return out.reshape(t, d).astype(src.dtype)


def forms(weighted: bool):
    """``{name: function of a draw}`` of the three forms, jitted under a
    name a profile shows."""
    def parent(a):
        if weighted:
            return moe.combine_rows(
                a["src"], a["gate"], a["order"], a["inverse"], a["live"])
        return moe._take_rows_bwd(
            a["gate"].shape[1], (a["inverse"], a["live"], None), a["src"])[0]

    def form_a(a):
        return gathered(
            a["src"], a["gate"] if weighted else None, a["order"], a["runs"])

    def form_b(a):
        return rows_to_tokens(
            a["src"], a["gate"] if weighted else None, *a["runs"])

    out = {}
    for name, f in (("parent", parent), ("gathered", form_a),
                    ("runs", form_b)):
        f.__name__ = "token_sum_" + name
        out[name] = jax.jit(f)
    return out


def errors(a, weighted: bool, names=("parent", "gathered", "runs"), fs=None):
    """Each form's largest error over the formula's largest entry."""
    want = formula(a, weighted)
    scale = float(jnp.max(jnp.abs(want)))
    fs = fs or forms(weighted)
    return {
        name: float(jnp.max(jnp.abs(fs[name](a).astype(_F32) - want)))
        / scale for name in names
    }


# ------------------------------------------------------------- the profile

def device_ms(f, a, calls: int = 5):
    """``(ms a call, [[operation, ms a call], ...])`` of ``f(a)`` on chip
    0, from a profile of ``calls`` calls after one that compiled."""
    jax.block_until_ready(f(a))
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp)
        for _ in range(calls):
            out = f(a)
        jax.block_until_ready(out)
        jax.profiler.stop_trace()
        (path,) = glob.glob(
            os.path.join(tmp, "plugins", "profile", "*", "*.xplane.pb"))
        data = jax.profiler.ProfileData.from_file(path)
    module, ops = 0.0, {}
    for plane in data.planes:
        if plane.name != "/device:TPU:0":
            continue
        for line in plane.lines:
            for event in line.events:
                if line.name == "XLA Modules":
                    module += event.duration_ns
                elif line.name == "XLA Ops":
                    name = event.name.split(" = ")[0].lstrip("%")
                    ops[name] = ops.get(name, 0.0) + event.duration_ns
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:5]
    return module / calls / 1e6, [[n, v / calls / 1e6] for n, v in top]


def measure(seed: int, cell: str, names):
    t, k, e, held, d = SHAPES[cell]
    a = draw(seed, t, k, e, held, d)
    c = a["src"].shape[0]
    out = {"shape": {"tokens": t, "top_k": k, "experts": e, "held": held,
                     "width": d, "pairs": t * k, "rows": c,
                     "live_pairs": int(a["runs"][1][-1]),
                     "tile_and_chunk": list(tiles(t, c, held))}}
    for weighted in (True, False):
        which = "combine_forward" if weighted else "take_backward"
        fs = forms(weighted)
        err = errors(a, weighted, names, fs)
        out[which] = {}
        for name in names:
            ms, top = device_ms(fs[name], a)
            out[which][name] = {"ms": ms, "error": err[name], "ops": top}
        print(cell, which, json.dumps(out[which]), flush=True)
    return out


def cliff(seed: int):
    """The parent's gated gather of 131,072 pairs (``combine_rows``'s
    forward) over source sizes and row widths, a quarter or an eighth of
    the pairs live: ms a call."""
    out = []
    parent = forms(True)["parent"]
    for d in (2048, 2304):
        for c in (12288, 24576, 32768, 49152):
            for e in (64, 128):
                a = draw(seed, 16384, 8, e, 16, d, rows=c)
                ms, top = device_ms(parent, a, calls=3)
                out.append({"width": d, "rows": c, "experts": e,
                            "source_mb": c * d * 2 / 1e6, "ms": ms,
                            "dearest": top[0]})
                print("cliff", json.dumps(out[-1]), flush=True)
    return out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, default=2200000277)
    parser.add_argument("--cells", default=",".join(SHAPES))
    parser.add_argument("--forms", default="parent,gathered,runs")
    parser.add_argument("--skip-cliff", action="store_true")
    parser.add_argument("--out", default="chiprun_out/moe_token_sum.json")
    opts = parser.parse_args(argv)
    if jax.default_backend() != "tpu":
        print("not on a TPU: nothing here is a chip's number", file=sys.stderr)
        return 3
    out = {"seed": opts.seed, "device": jax.devices()[0].device_kind,
           "cells": {}}
    for cell in opts.cells.split(","):
        # The one gather of form A is read where the issue asks for it.
        out["cells"][cell] = measure(opts.seed, cell, [
            name for name in opts.forms.split(",")
            if name != "gathered" or cell in ("mellum2", "sdar")
        ])
    if not opts.skip_cliff:
        out["cliff"] = cliff(opts.seed)
    os.makedirs(os.path.dirname(opts.out), exist_ok=True)
    with open(opts.out, "w") as fh:
        json.dump(out, fh, indent=1)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
