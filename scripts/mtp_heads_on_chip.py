"""The comparison that decides ``correct`` for a cell with a
multi-token-prediction module, run against its CONTROLS on the chip at the
published widths: the reference with its trunk rounded to bfloat16 (what
the program's own rounding reads) and to float8 (the precision below the
stated one), and each departure the builder lists, on seeded weights as
``model.init`` draws them (the state a 30 s window leaves is 40 steps of a
20,000-step warm-up from it).

    chiprun -- python scripts/mtp_heads_on_chip.py [--cell <cell>]
        [--seeds 11,12] [--controls trunk_bfloat16,trunk_float8,<departure>...]

Prints one JSON line a control: the four errors ``check_heads`` holds to
their limits and which of them refuse it. ``chiprun_out/mtp_heads.json``
keeps them. PERF.md section 6 (PR 67) has the readings.
"""
import argparse
import json
import os
import sys
import time

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "benchmark"), ROOT]

import harness  # noqa: E402

from raydp_tpu.models import step as model_step  # noqa: E402


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--cell", default="glm_4_7_flash.fit_mtp_s8192")
    parser.add_argument("--seeds", default="2147483659")
    parser.add_argument("--controls", default="")
    args = parser.parse_args()
    cell = harness.load_cell(ROOT, args.cell)
    builder, sizes = cell.model, cell.sizes
    lm = builder.model(sizes)
    controls = [c for c in args.controls.split(",") if c] or (
        ["program", "trunk_bfloat16", "trunk_float8"]
        + list(builder.DEPARTURES))
    rows = []
    for seed in map(int, args.seeds.split(",")):
        ids = builder.check_batch(sizes, cell.traffic, seed)
        params = jax.jit(lambda key: model_step.parameters(nn.unbox(
            lm.init(key, jnp.zeros((1, ids.shape[1]), jnp.int32))))
        )(jax.random.PRNGKey(seed % (2 ** 31)))
        for control in controls:
            how = {}
            if control.startswith("trunk_"):
                how["trunk"] = jnp.dtype({
                    "trunk_bfloat16": jnp.bfloat16,
                    "trunk_float8": jnp.float8_e4m3fn}[control])
            elif control != "program":
                how["depart"] = control
            t0 = time.perf_counter()
            checks, detail = builder.check_heads(lm, params, ids, sizes, **how)
            limits = detail["limits"]
            rows.append({
                "seed": seed, "control": control, **detail["errors"],
                "refused_by": sorted(
                    k for k, v in detail["errors"].items()
                    if not (np.isfinite(v) and v <= limits[k])),
                "ref_loss_main": detail["reference"]["loss_main"],
                "ref_loss_mtp": detail["reference"]["loss_mtp"],
                "seconds": time.perf_counter() - t0,
            })
            print(json.dumps(rows[-1]), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "mtp_heads.json"), "w") as f:
        json.dump({"device": jax.devices()[0].device_kind, "rows": rows}, f,
                  indent=1)


if __name__ == "__main__":
    main()
