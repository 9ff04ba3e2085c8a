"""What only a chip can say of ``ops/causal_conv.py``'s kernels with the
L2 norm of each head inside, in one call:

    chiprun --timeout 900 -- python scripts/causal_conv_on_chip.py \
        [--seed N] [--out chiprun_out/causal_conv_on_chip.json]

ONE of a Kimi Delta Attention layer's q (or k) convolutions at the cell's
shape (one sequence of 16,384 tokens, 4,096 channels in heads of 128, four
taps, no bias; bfloat16 in and out, the taps float32), forward alone and
forward + backward, by three forms of ``silu(conv4(x))`` normalised a head,
times ``d_k^-1/2``, in bfloat16: ``fused`` (the kernels with the norm
inside: ``models/kda.QKVConv``'s path on a TPU at heads of whole
registers), ``kernel_then_norm`` (the kernels writing float32 and the norm
as ``jax.numpy`` after them: its path before the norm could be inside)
and ``jnp`` (the ``jax.numpy`` convolution and norm: its path anywhere
else). Milliseconds by the host's clock around calls that end in
``block_until_ready``, the share of the time the bytes no algorithm can
avoid would take (``x`` and the result once forward; ``x``, the cotangent
and ``dx`` backward, at the chip's 819 GB/s), and how far each form's
result and gradients are from the ``jax.numpy`` form's at a float32 ``x``
(the reference the tests use: differentiated in float32, rounded once).
Anywhere but on a TPU it exits 3 and prints no number.

``tests/test_causal_conv_kernel.py`` runs :func:`measure` at a tiny shape
in the Pallas interpreter, so the script cannot rot unseen.
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

from raydp_tpu.models.kda import L2_EPS, QKVConv  # noqa: E402
from raydp_tpu.models.mamba import causal_depthwise_conv  # noqa: E402
from raydp_tpu.ops.causal_conv import Unit, causal_conv_silu  # noqa: E402

# (tokens, channels, a head's width, taps) of q's and k's call in the cell.
KIMI = (16384, 4096, 128, 4)
HBM_BYTES_PER_S = 819e9     # TPU v5e (benchmark/peaks.json)
FORMS = ("fused", "kernel_then_norm", "jnp")


def operands(shape, seed: int, dtype=jnp.bfloat16):
    """``x`` as a projection's output (unit normal), the taps as
    ``CausalConv1d`` draws them (uniform in ±1/√taps) and a cotangent."""
    s, channels, _, taps = shape
    rng = np.random.default_rng(seed)
    bound = taps ** -0.5
    return (
        jnp.asarray(rng.standard_normal((1, s, channels)), dtype),
        jnp.asarray(rng.uniform(-bound, bound, (taps, channels)), jnp.float32),
        jnp.asarray(rng.standard_normal((1, s, channels)), dtype),
    )


def least_bytes(shape, itemsize: int = 2) -> tuple:
    """``(forward, forward + backward)``: what one call cannot avoid
    moving (``x`` and the result; then ``x``, the cotangent and ``dx``)."""
    s, channels, _, _ = shape
    return 2 * s * channels * itemsize, 5 * s * channels * itemsize


def _normed(y, width: int, dtype):
    """``QKVConv``'s arithmetic on a float32 ``y`` [B, S, C]."""
    heads = y.reshape(*y.shape[:-1], -1, width)
    return (QKVConv.unit(heads) * width ** -0.5).reshape(y.shape).astype(dtype)


def forms_of(shape, dtype, interpret: bool = False, blocks=None) -> dict:
    """``{form: f(x, taps) -> normalised, scaled, in dtype}``."""
    width = shape[2]
    kernel = dict(interpret=interpret, blocks=blocks)
    return {
        "fused": lambda x, w: causal_conv_silu(
            x, w, dtype=dtype, unit=Unit(width, L2_EPS),
            scale=width ** -0.5, **kernel),
        "kernel_then_norm": lambda x, w: _normed(
            causal_conv_silu(x, w, dtype=jnp.float32, **kernel), width,
            dtype),
        "jnp": lambda x, w: _normed(
            jax.nn.silu(causal_depthwise_conv(x, w)), width, dtype),
    }


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
            forms=FORMS, interpret: bool = False, blocks=None) -> dict:
    """The shape by each of ``forms``: ``{form: {forward_ms,
    forward_backward_ms, share_of_least: {forward, forward_backward}}}``
    and ``apart``, the largest difference of each form's result, ``dx``
    and ``dtaps`` from the float32 reference's as a share of the largest
    entry. ``interpret`` and ``blocks`` are a test's (the Pallas
    interpreter at its own tiling: its times are no chip's)."""
    x, w, dn = operands(shape, seed, dtype)
    fns = forms_of(shape, dtype, interpret, blocks)
    least = least_bytes(shape, jnp.dtype(dtype).itemsize)

    def pair(fn):
        """The result and the pullback in one program (the pullback alone
        would need no forward call of the fused kernels: their residual
        is the operands)."""
        def both(x, w, dn):
            y, vjp = jax.vjp(fn, x, w)
            return (y, *vjp(dn))

        return jax.jit(both)

    want = pair(fns["jnp"])(x.astype(jnp.float32), w, dn)
    out = {"apart": {}}
    for form in forms:
        forward, both = jax.jit(fns[form]), pair(fns[form])
        out["apart"][form] = {
            name: _apart(a, b) for name, a, b in zip(
                ("y", "dx", "dtaps"), both(x, w, dn), want)
        }
        times = _ms(forward, (x, w), repeats), _ms(both, (x, w, dn), repeats)
        out[form] = {
            "forward_ms": times[0],
            "forward_backward_ms": times[1],
            "share_of_least": {
                "forward": least[0] / HBM_BYTES_PER_S * 1e3 / times[0],
                "forward_backward": (
                    least[1] / HBM_BYTES_PER_S * 1e3 / times[1]),
            },
        }
    return out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--repeats", type=int, default=10)
    parser.add_argument(
        "--out", default="chiprun_out/causal_conv_on_chip.json")
    args = parser.parse_args(argv)
    if jax.default_backend() != "tpu":
        print("not on a TPU: nothing here is a chip's number", file=sys.stderr)
        return 3

    found = measure(KIMI, args.seed, args.repeats)
    report = {"device": jax.devices()[0].device_kind, "shape": KIMI, **found}
    print(json.dumps(report), flush=True)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
