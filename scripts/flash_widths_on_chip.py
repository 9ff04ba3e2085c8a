"""The flash kernels alone on the chip at a latent-attention shape, forward
and backward, by tile edge: milliseconds a call and the share of the
chip's bf16 peak over the causal pairs that exist.

    chiprun -- python scripts/flash_widths_on_chip.py [--d 256 --dv 256
        --heads 20 --seq 8192 --batch 2 --tiles 1024,512]

PR 67 ran it at GLM-4.7-Flash's 20 heads of 256 / 256 (PERF.md section 6)
beside Xing4.0's 192 / 128 at the same pairs. Results go to stdout and to
``chiprun_out/flash_widths.json``.
"""
import argparse
import json
import os
import time

import jax
import jax.numpy as jnp

from raydp_tpu.ops.flash_attention import backward_is_fused, flash_attention

PEAK = 197e12   # bf16 FLOP/s of one v5e chip (benchmark/peaks.json)


def timed(fn, *args, repeats=10):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(repeats):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / repeats


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--shapes", default="256:256,192:128,128:128")
    parser.add_argument("--heads", type=int, default=20)
    parser.add_argument("--seq", type=int, default=8192)
    parser.add_argument("--batch", type=int, default=2)
    parser.add_argument("--tiles", default="1024,512")
    args = parser.parse_args()
    device = jax.devices()[0]
    rows = []
    for shape in args.shapes.split(","):
        d, dv = map(int, shape.split(":"))
        keys = jax.random.split(jax.random.PRNGKey(0), 4)
        like = (args.batch, args.seq, args.heads)
        q, k = (jax.random.normal(key, like + (d,), jnp.bfloat16)
                for key in keys[:2])
        v, g = (jax.random.normal(key, like + (dv,), jnp.bfloat16)
                for key in keys[2:])
        pairs = args.batch * args.heads * args.seq * (args.seq + 1) / 2
        forward_flops = 2.0 * pairs * (d + dv)
        backward_flops = 2.0 * pairs * (3 * d + 2 * dv)
        for tile in map(int, args.tiles.split(",")):
            def attend(q, k, v, tile=tile):
                return flash_attention(
                    q, k, v, causal=True, block_q=tile, block_kv=tile)

            forward = jax.jit(attend)
            both = jax.jit(lambda q, k, v, g: jax.vjp(attend, q, k, v)[1](g))
            f_s = timed(forward, q, k, v)
            b_s = timed(both, q, k, v, g) - f_s
            rows.append({
                "d": d, "d_v": dv, "tile": tile, "seq": args.seq,
                "heads": args.heads, "batch": args.batch,
                "backward_fused": backward_is_fused(args.seq, d, dv, 2),
                "forward_ms": f_s * 1e3, "backward_ms": b_s * 1e3,
                "forward_share_of_peak": forward_flops / f_s / PEAK,
                "backward_share_of_peak": backward_flops / b_s / PEAK,
                "both_share_of_peak":
                (forward_flops + backward_flops) / (f_s + b_s) / PEAK,
            })
            print(json.dumps(rows[-1]), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/flash_widths.json", "w") as f:
        json.dump({"device": device.device_kind, "rows": rows}, f, indent=1)


if __name__ == "__main__":
    main()
