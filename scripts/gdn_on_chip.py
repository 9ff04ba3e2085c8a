"""What only a chip can say of ``ops/gdn.py``'s kernels, in one call:

    chiprun --timeout 900 -- python scripts/gdn_on_chip.py \
        [--heads-a-step 6 10] [--seed N] [--out chiprun_out/gdn_on_chip.json]

ONE layer's scan at Olmo-Hybrid's shapes (one sequence of 4,096 tokens, 30
heads, keys of 96 and values of 192, chunks of 64; bfloat16, random
operands with the decays a trained layer has), forward alone and forward
+ backward, by three forms: ``kernels`` (the Pallas rule, whose blocks
take 96 and 192 as they are), ``keys_128`` (the same kernels on ``q`` and
``k`` zero-padded to 128 key channels inside the timed call: the other
lane layout, the same mathematics) and ``jnp`` (the plain rule).
Milliseconds by the host's clock around calls that end in
``block_until_ready``, microseconds a chunk-head, the share of the time
the bytes no algorithm can avoid would take (``q``, ``k``, ``v``, ``g``,
``beta`` and ``o`` once forward and twice backward at the chip's 819
GB/s), how far the forms' results and gradients are apart, each of the
five kernels alone on one segment's chunks (µs a chunk-head; the forward
kernel less the rebuild is what keeping ``T`` saves), and forward +
backward at each ``--heads-a-step`` of the state kernels. Anywhere but on
a TPU it exits 3 and prints no number.

``tests/test_gdn_kernel.py`` runs :func:`measure` at a tiny shape in the
Pallas interpreter, so the script cannot rot unseen.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from raydp_tpu.ops import gdn  # noqa: E402
from raydp_tpu.ops import kda  # noqa: E402

# (tokens, heads, d_k, d_v, chunk) of one layer's call in the cell.
OLMO = (4096, 30, 96, 192, 64)
HBM_BYTES_PER_S = 819e9     # TPU v5e (benchmark/peaks.json)
NAMES = ("q", "k", "v", "g", "beta")


def operands(shape, seed: int, dtype=jnp.bfloat16):
    """A layer's operands as a trained one has them: ``q`` and ``k`` of
    unit length a head (``q`` times ``d_k^-1/2``), ``g = −A · dt`` with
    ``dt`` drawn as published (0.001 to 0.1) and ``A`` in [1, 16], ``beta``
    in (0, 2)."""
    s, h, d_k, d_v, _ = shape
    rng = np.random.default_rng(seed)

    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(rng.standard_normal((1, s, h, d_k))) * d_k ** -0.5
    k = unit(rng.standard_normal((1, s, h, d_k)))
    v = rng.standard_normal((1, s, h, d_v))
    g = -rng.uniform(1.0, 16.0, (h,)) * np.exp(
        rng.uniform(np.log(1e-3), np.log(1e-1), (1, s, h)))
    beta = 2.0 / (1.0 + np.exp(-rng.standard_normal((1, s, h))))
    return (jnp.asarray(q, dtype), jnp.asarray(k, dtype),
            jnp.asarray(v, dtype), jnp.asarray(g, jnp.float32),
            jnp.asarray(beta, jnp.float32))


def least_bytes(shape, itemsize: int = 2) -> int:
    """What one layer's scan cannot avoid moving, forward and backward."""
    s, h, d_k, d_v, _ = shape
    return 3 * s * h * (2 * d_k * itemsize + 2 * d_v * itemsize + 2 * 4)


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


def _keys_128(q, k, v, g, beta, chunk):
    """The kernels on 128 key channels: zeros add nothing to ``q kᵀ``,
    ``k kᵀ``, ``W S`` or the state's rows past ``d_k``."""
    pad = ((0, 0),) * 3 + ((0, -q.shape[-1] % 128),)
    return gdn.gdn_chunked(
        jnp.pad(q, pad), jnp.pad(k, pad), v, g, beta, chunk, kernels=True)


def segment_chunks(shape) -> int:
    """Chunks a segment of a sequence of this shape (``segment_walk``'s)."""
    return math.gcd(shape[0] // shape[-1], kda.SEGMENT_CHUNKS)


def kernels_alone(shape, args, repeats: int) -> dict:
    """µs a chunk-head of each kernel on ONE segment's chunks."""
    chunk = shape[-1]
    count = segment_chunks(shape)
    xs = kda._chunks(chunk, *(a[:, :count * chunk] for a in args))
    interpret = kda._interpret()
    *five, T = gdn._forward_call(*xs, keep=True, interpret=interpret)
    b, n, h, c, d_v = five[0].shape
    heads = gdn.state_heads(h)
    rows = jnp.broadcast_to(gdn._end(xs[3])[..., None], (b, n, h, 1, d_v))
    state = jnp.zeros((b, h, xs[1].shape[-1], d_v), jnp.float32)
    out, entered, w, left = gdn._state_forward_call(
        (*five, rows), state, heads=heads, keeps=True, interpret=interpret)
    kept = (*five[1:], rows, entered, w)
    calls = {
        "gdn_chunk_forward": (lambda *a: gdn._forward_call(
            *a, keep=True, interpret=interpret), xs),
        "gdn_chunk_rebuild": (lambda *a: gdn._forward_call(
            *a, interpret=interpret), (*xs, T)),
        "gdn_chunk_backward": (lambda *a: gdn._backward_call(
            a[:6], a[6:], interpret=interpret), (*xs, T, *five)),
        "gdn_state_forward": (lambda *a: gdn._state_forward_call(
            a[:6], a[6], heads=heads, keeps=True, interpret=interpret),
            (*five, rows, state)),
        "gdn_state_backward": (lambda *a: gdn._state_backward_call(
            a[:7], a[7], a[8], heads=heads, interpret=interpret),
            (*kept, out, left)),
    }
    return {
        name: _ms(fn, operands_, repeats) * 1e3 / (b * n * h)
        for name, (fn, operands_) in calls.items()
    }


def measure(shape, seed: int = 0, repeats: int = 10, dtype=jnp.bfloat16,
            forms=("kernels", "keys_128", "jnp"), heads_a_step=()) -> dict:
    """The shape by each of ``forms``: ``{form: {forward_ms,
    forward_backward_ms, us_a_chunk_head, share_of_least}}``; ``apart``,
    the largest difference of each form's result and gradients from the
    plain rule's as a share of the largest entry; ``alone``, each kernel
    on a segment (:func:`kernels_alone`); and ``heads_a_step``, forward +
    backward ms of the kernels at each of those group sizes. Off a TPU
    the kernels run in the Pallas interpreter (a test's: its times are no
    chip's)."""
    s, h, _, _, chunk = shape
    args = operands(shape, seed, dtype)
    scans = {
        "kernels": lambda *a: gdn.gdn_chunked(*a, chunk, kernels=True),
        "keys_128": lambda *a: _keys_128(*a, chunk),
        "jnp": lambda *a: gdn.gdn_chunked(*a, chunk, kernels=False),
    }

    def pair(scan):
        return jax.jit(jax.value_and_grad(
            lambda *a: jnp.sum(jnp.sin(scan(*a).astype(jnp.float32))),
            argnums=tuple(range(5))))

    out, results = {}, {}
    for form in forms:
        forward, both = jax.jit(scans[form]), pair(scans[form])
        results[form] = (forward(*args), *both(*args)[1])
        pair_ms = _ms(both, args, repeats)
        out[form] = {
            "forward_ms": _ms(forward, args, repeats),
            "forward_backward_ms": pair_ms,
            "us_a_chunk_head": pair_ms * 1e3 / (s // chunk * h),
            "share_of_least": (
                least_bytes(shape, jnp.dtype(dtype).itemsize)
                / HBM_BYTES_PER_S * 1e3 / pair_ms),
        }
    if "jnp" in results:
        out["apart"] = {
            form: {
                name: _apart(got, want) for name, got, want in zip(
                    ("o",) + NAMES, results[form], results["jnp"])
            } for form in results if form != "jnp"
        }
    out["alone"] = kernels_alone(shape, args, repeats)
    was = gdn.HEADS_A_STEP
    try:
        out["heads_a_step"] = {}
        for heads in heads_a_step:
            gdn.HEADS_A_STEP = heads
            out["heads_a_step"][str(gdn.state_heads(h))] = _ms(
                pair(scans["kernels"]), args, repeats)
    finally:
        gdn.HEADS_A_STEP = was
    return out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--heads-a-step", nargs="*", type=int,
                        default=[3, 6, 10])
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--repeats", type=int, default=10)
    parser.add_argument("--out", default="chiprun_out/gdn_on_chip.json")
    args = parser.parse_args(argv)
    if jax.default_backend() != "tpu":
        print("not on a TPU: nothing here is a chip's number", file=sys.stderr)
        return 3

    found = measure(OLMO, args.seed, args.repeats,
                    heads_a_step=args.heads_a_step)
    report = {"device": jax.devices()[0].device_kind,
              "heads_a_step_kept": gdn.HEADS_A_STEP, "shape": OLMO, **found}
    print(json.dumps(report), flush=True)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
