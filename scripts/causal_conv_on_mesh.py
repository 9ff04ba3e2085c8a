"""The causal convolution's kernels (``ops/causal_conv.py``) on a device
mesh, on the chips: what only several chips can show.

    chiprun --chips 4 --timeout 1500 -- python scripts/causal_conv_on_mesh.py

For each mesh over the host's chips (``dp`` = all of them, then ``dp`` =
2 by ``tp``): (1) the call alone at Granite's and Kimi Linear's shapes,
one sequence a ``dp`` row: forward, ``dx``, ``dkernel`` and ``dbias``
inside the ``shard_map`` against the ``jax.numpy`` form the compiler
partitions itself; (2) ``JAXEstimator``'s own train step of two Granite
Mamba-2 layers at the published widths, S = 4,096, with the model's mesh
told (the kernels, ``conv/kernel_calls`` 2) and not told (the
``jax.numpy`` form, ``conv/jnp_calls`` 2: a host of several chips and no
mesh): both compile, the losses of three steps agree, and the step time
of each is read. Writes ``chiprun_out/causal_conv_on_mesh.json``; exits 1
where a comparison fails. About 4 minutes on four chips.
"""
from __future__ import annotations

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from raydp_tpu.models import CausalLM  # noqa: E402
from raydp_tpu.models.mamba import causal_depthwise_conv  # noqa: E402
from raydp_tpu.models.transformer import granite_h_micro  # noqa: E402
from raydp_tpu.ops.causal_conv import causal_conv_silu  # noqa: E402
from raydp_tpu.parallel import MeshSpec  # noqa: E402
from raydp_tpu.train import JAXEstimator  # noqa: E402
from raydp_tpu.utils.profiling import metrics  # noqa: E402

F32, BF16 = jnp.float32, jnp.bfloat16
# (S, channels, bias, output dtype, sequence_minor), as the mixers call.
SHAPES = {
    "granite": (4096, 4352, True, BF16, True),
    "kimi": (16384, 4096, False, F32, False),
}
# One rounding of a bfloat16 array; a float32 sum over S tokens.
TOLERANCE = {jnp.dtype(BF16): 2.0 ** -7, jnp.dtype(F32): 1e-4}


def call_alone(mesh, name: str) -> dict:
    s, channels, bias, out, minor = SHAPES[name]
    rows = mesh.shape["dp"]
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.device_put(
        jax.random.normal(keys[0], (rows, s, channels), F32).astype(BF16),
        NamedSharding(mesh, P("dp")))
    kernel = jax.random.uniform(keys[1], (4, channels), F32, -0.5, 0.5)
    b = jax.random.uniform(keys[2], (channels,), F32, -0.5, 0.5) if (
        bias) else None
    dy = jax.random.normal(keys[3], (rows, s, channels), F32).astype(out)

    def both(form):
        def run(x, kernel, b, dy):
            y, vjp = jax.vjp(form, x, kernel, b)
            return (y, *vjp(dy))
        return jax.jit(run)

    got = both(lambda *a: causal_conv_silu(
        *a, dtype=out, sequence_minor=minor, mesh=mesh))(x, kernel, b, dy)
    # The reference differentiates at x in float32 and rounds dx once.
    want = both(lambda x, *a: jax.nn.silu(causal_depthwise_conv(
        x.astype(F32), *a)).astype(out))(x, kernel, b, dy)
    errors = {}
    for part, a, w in zip(("y", "dx", "dkernel", "dbias"), got, want):
        if a is None:
            continue
        a, w = np.asarray(a, np.float32), np.asarray(w, np.float32)
        errors[part] = float(np.abs(a - w).max() / np.abs(w).max())
    limits = {"y": TOLERANCE[jnp.dtype(out)], "dx": TOLERANCE[jnp.dtype(BF16)],
              "dkernel": 1e-4, "dbias": 1e-4}
    return {"relative_error": errors,
            "ok": all(errors[k] <= limits[k] for k in errors)}


def train_steps(spec: MeshSpec, told: bool, steps: int = 3,
                seq: int = 4096, **sizes) -> dict:
    """``sizes`` and ``seq``: a rehearsal's, off the chip."""
    mesh = spec.build()
    cfg = granite_h_micro(**{**dict(
        n_layers=2, layer_types=("mamba", "mamba"), vocab_size=8192,
        mesh=mesh if told else None), **sizes})
    batch = 2 * spec.dp
    est = JAXEstimator(
        model=CausalLM(cfg), optimizer=optax.adamw(1e-4), loss="lm_ce",
        self_supervised=True, feature_dtype=np.int32, batch_size=batch,
        mesh=spec, seed=0,
    )
    ids = np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=(batch, seq)).astype(np.int32)
    est._init_state(ids)
    gauges = {name: metrics.gauge_value(name)
              for name in ("conv/kernel_calls", "conv/jnp_calls")}
    step = jax.jit(est._make_train_step(), donate_argnums=(0,))
    xd, yd = est._shard_batch(ids, None)
    state, losses, times = est._state, [], []
    for at in range(steps + 1):       # the first compiles
        t0 = time.perf_counter()
        state, loss, *_ = step(state, xd, yd, jax.random.PRNGKey(at))
        losses.append(float(loss))
        times.append(time.perf_counter() - t0)
    return {"gauges": gauges, "losses": losses,
            "step_ms": round(1e3 * min(times[1:]), 2)}


def main() -> int:
    chips = jax.device_count()
    out = {"device": jax.devices()[0].device_kind, "chips": chips}
    specs = [MeshSpec(dp=chips)] + (
        [MeshSpec(dp=2, tp=chips // 2)] if chips >= 4 else [])
    ok = True
    for spec in specs:
        entry = {"call": {name: call_alone(spec.build(), name)
                          for name in SHAPES}}
        kernels, plain = train_steps(spec, True), train_steps(spec, False)
        entry["step"] = {"kernels": kernels, "jnp": plain}
        agree = np.allclose(kernels["losses"], plain["losses"], rtol=2e-3)
        taken = (kernels["gauges"] == {"conv/kernel_calls": 2,
                                       "conv/jnp_calls": 0}
                 and plain["gauges"] == {"conv/kernel_calls": 0,
                                         "conv/jnp_calls": 2})
        entry["ok"] = bool(agree and taken and all(
            c["ok"] for c in entry["call"].values()))
        ok = ok and entry["ok"]
        out[str(spec.axis_sizes)] = entry
        print(spec.axis_sizes, json.dumps(entry), flush=True)
    out["ok"] = ok
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/causal_conv_on_mesh.json", "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({"ok": ok}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
