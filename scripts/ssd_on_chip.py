"""What only a chip can say of ``ops/ssd.py``'s kernels, in one call:

    chiprun --timeout 900 -- python scripts/ssd_on_chip.py \
        [--cells nemotron granite] [--seed N] \
        [--out chiprun_out/ssd_on_chip.json]

ONE layer's scan at a cell's shapes (one sequence; Nemotron-3-Nano: 16,384
tokens, 64 heads of 64 in 8 groups, state 128, chunks of 128; Granite 4.0
H Micro: 4,096 tokens, 64 heads of 64 in one group, chunks of 256;
bfloat16, random operands with the decays a trained layer has), forward
alone and forward + backward, by the Pallas kernels (``ssd_scan_packed``,
``x``, ``B`` and ``C`` laid side by side inside the timed call) and by the
``jax.numpy`` form (``ssd_chunked``): milliseconds by the host's clock
around calls that end in ``block_until_ready``, the share of the time the
bytes no algorithm can avoid would take (``x``, ``B``, ``C``, ``dt`` and
``y`` once forward and twice backward, as the benchmark's
``ssd_bytes_per_step`` counts them, at the chip's 819 GB/s), and how far
the two forms' results and gradients are apart. Anywhere but on a TPU it
exits 3 and prints no number.

``tests/test_ssd_kernel.py`` runs :func:`measure` at a tiny shape in the
Pallas interpreter (``interpret=True``), so the script cannot rot unseen.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from raydp_tpu.ops import ssd  # noqa: E402

# (tokens, heads, head_dim, groups, state, chunk) of one layer's call.
CELLS = {
    "nemotron": (16384, 64, 64, 8, 128, 128),
    "granite": (4096, 64, 64, 1, 128, 256),
}
HBM_BYTES_PER_S = 819e9     # TPU v5e (benchmark/peaks.json)
NAMES = ("x", "dt", "A", "B", "C", "D")


def operands(shape, seed: int, dtype=jnp.bfloat16):
    """A layer's operands as a trained one has them: ``dt`` the softplus
    of a bias drawn as published (0.001 to 0.1), ``A`` in −[1, 16]."""
    s, h, p, g, n, _ = shape
    rng = np.random.default_rng(seed)

    def normal(*dims):
        return jnp.asarray(rng.standard_normal(dims), jnp.float32)

    dt = jnp.asarray(
        np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), (1, s, h))),
        jnp.float32)
    A = -jnp.asarray(rng.uniform(1.0, 16.0, (h,)), jnp.float32)
    return (normal(1, s, h, p).astype(dtype), dt, A,
            normal(1, s, g, n).astype(dtype), normal(1, s, g, n).astype(dtype),
            normal(h))


def least_bytes(shape, itemsize: int = 2) -> int:
    """What one layer's scan cannot avoid moving, forward and backward
    (the benchmark's ``ssd_bytes_per_step`` for one layer)."""
    s, h, p, g, n, _ = shape
    return 3 * s * (2 * h * p * itemsize + 2 * g * n * itemsize + 4 * h)


def _ms(fn, args, repeats: int) -> float:
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(3):
        start = time.perf_counter()
        for _ in range(repeats):
            out = fn(*args)
        jax.block_until_ready(out)
        times.append((time.perf_counter() - start) / repeats * 1e3)
    return min(times)


def _apart(got, want) -> float:
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def measure(shape, seed: int = 0, repeats: int = 10, dtype=jnp.bfloat16,
            interpret: bool = False) -> dict:
    """One cell's shape by both forms: ``{form: {forward_ms,
    forward_backward_ms, share_of_least}}`` and ``apart``, the largest
    difference between the forms' results as a share of the largest
    entry, for ``y`` and each gradient. ``interpret`` runs the kernels in
    the Pallas interpreter (a test's, off the chip: its times are no
    chip's)."""
    chunk = shape[-1]
    args = operands(shape, seed, dtype)

    def kernels(x, dt, A, B, C, D):
        b, s = x.shape[:2]
        xbc = jnp.concatenate(
            [a.reshape(b, s, -1) for a in (x, B, C)], axis=-1)
        return ssd.ssd_scan_packed(
            xbc, dt, A, D, chunk, *B.shape[2:], interpret=interpret
        ).reshape(x.shape)

    scans = {
        "kernels": kernels,
        "jnp": lambda *a: ssd.ssd_chunked(*a, chunk),
    }
    out, results = {}, {}
    for form, scan in scans.items():
        forward = jax.jit(scan)
        both = jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(jnp.sin(scan(*a).astype(jnp.float32))),
            argnums=tuple(range(6))))
        results[form] = (forward(*args), *both(*args)[1])
        pair_ms = _ms(both, args, repeats)
        out[form] = {
            "forward_ms": _ms(forward, args, repeats),
            "forward_backward_ms": pair_ms,
            "share_of_least": (
                least_bytes(shape, jnp.dtype(dtype).itemsize)
                / HBM_BYTES_PER_S * 1e3 / pair_ms),
        }
    out["apart"] = {
        name: _apart(got, want) for name, got, want in zip(
            ("y",) + NAMES, results["kernels"], results["jnp"])
    }
    return out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--cells", nargs="+", default=list(CELLS),
                        choices=list(CELLS))
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--repeats", type=int, default=10)
    parser.add_argument("--out", default="chiprun_out/ssd_on_chip.json")
    args = parser.parse_args(argv)
    if jax.default_backend() != "tpu":
        print("not on a TPU: nothing here is a chip's number", file=sys.stderr)
        return 3

    report = {"device": jax.devices()[0].device_kind,
              "heads_a_step": ssd.HEADS_A_STEP, "cells": {}}
    for cell in args.cells:
        found = measure(CELLS[cell], args.seed, args.repeats)
        report["cells"][cell] = found
        print(cell, json.dumps(found), flush=True)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
