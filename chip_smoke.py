"""chip_smoke.py — does the system still start on the chip?

Drives raydp_tpu's main paths once on a TPU, through the entry points a
user calls, at the full width of BERT-base (12 layers x 768, 12 heads,
vocab 30522, bf16 compute, ~109M parameters; weights random from a seed):

  train  cluster ETL (2 CPU workers) -> JAXEstimator.fit_on_df on
         SequenceClassifier(bert_base(max_len=128)), batch 128 per chip,
         a few steps each with epoch_mode "stream" and "scan" over
         MeshSpec(dp=all devices); then, in the same process, the Pallas
         flash-attention kernels (forward + grad, compiled by Mosaic,
         against reference_attention) and one fit with
         attention_impl="flash".
  serve  ReplicaGroup(replicas=1, mode="decode") whose replica builds a
         12 x 768 bf16 decode engine on the chip and answers requests of
         different prompt lengths through the RequestQueue.
  gang   a driver that ran a cluster ETL calls fit_spmd(world_size=1) on
         the taxi MLP; the rank reports its platform from inside the
         shipped function.

One process may hold a chip, so this parent never creates a JAX backend:
each phase runs in a process of its own, one after another, and in the
serve and gang phases the phase process stays off the chip too (the
replica / the rank holds it, and the phase checks that it did not).

Only when every phase passed on a TPU: exit code 0 and two stdout lines,
a summary of what was printed and not judged ({"jax": ..., "seconds":
..., "phases": {...}, "claim": null}) and then, as the last line, the
verdict with exactly these keys:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

No TPU, a missing repo or any failed phase: a non-zero exit, the failing
process's log tail on stderr, and nothing on stdout. There is no way to
run this on a CPU; to
debug a phase at a tiny size, import the module and call ``run_train`` /
``run_serve`` / ``run_gang`` with small sizes and ``platform="cpu"``.

    python chip_smoke.py                              # all phases
    python chip_smoke.py --phase train --mesh dp=2,tp=2   # one phase
"""
from __future__ import annotations

import glob
import json
import os
import signal
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
PHASES = ("train", "serve", "gang")
# Wall-clock cap per phase; together they stay under the driver's 1200 s.
PHASE_TIMEOUT_S = {"train": 660, "serve": 240, "gang": 200}
RESULT_TAG = "CHIP_SMOKE_PHASE_RESULT "
REPLICA_TAG = "CHIP_SMOKE_REPLICA_DEVICE "
NO_TPU_EXIT = 3

FULL = {
    # bert_base() defaults ARE the full width; only the length is set.
    "model": {"max_len": 128},
    "seq": 128,
    "per_chip_batch": 128,
    "steps_per_epoch": 4,
    # (epoch_mode, attention_impl, epochs): epoch 0 compiles, epoch 1 is
    # the steady one the step time is read from.
    "fits": (("stream", "dense", 2), ("scan", "dense", 2),
             ("stream", "flash", 1)),
    "serve_model": {"d_model": 768, "n_heads": 12, "n_layers": 12,
                    "d_ff": 3072},
    # (prompt length, tokens asked for)
    "serve_requests": ((5, 8), (17, 12), (40, 6), (90, 16)),
    "taxi_rows": 20_000,
}

# Flash kernel vs reference_attention, bf16 inputs, f32 reference: the
# largest absolute error allowed, as a share of the reference's largest
# magnitude (bf16 has 8 bits of mantissa; the PR 21 chip run measured
# 0.4-0.6% on every output).
FLASH_TOLERANCE = 2e-2
# (batch, seq, heads, head_dim, causal): the BERT-base training shape
# and the long causal one.
FLASH_SHAPES = ((128, 128, 12, 64, False), (2, 2048, 12, 64, True))


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", file=sys.stderr, flush=True)


# ------------------------------------------------------------------ train

def require_tpu() -> dict:
    """Create the backend in THIS process and insist it is a TPU."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(
            f"chip_smoke: no TPU: jax.devices() reports platform "
            f"{devs[0].platform!r}; this script checks the chip and does "
            "not run on anything else",
            file=sys.stderr, flush=True,
        )
        sys.exit(NO_TPU_EXIT)
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }


def _glue_frame(n_raw: int, seq: int, vocab: int):
    """Tokenized-GLUE stand-in: ``marker`` says whether token 7 occurs;
    every fifth row is flagged invalid for the ETL filter to drop."""
    import numpy as np
    import pandas as pd

    rng = np.random.default_rng(0)
    ids = rng.integers(10, vocab, size=(n_raw, seq))
    pos = rng.random(n_raw) < 0.5
    ids[pos, rng.integers(0, seq, pos.sum())] = 7
    cols = {f"t{i}": ids[:, i] for i in range(seq)}
    cols["marker"] = pos.astype(np.int64)
    cols["valid"] = (np.arange(n_raw) % 5 != 4).astype(np.int64)
    return pd.DataFrame(cols)


def _worker_probe(table):
    """Runs on an ETL worker: which platform was it given, and did it
    end up with a JAX backend?"""
    import pyarrow as pa

    backend = "jax-not-imported"
    jax = sys.modules.get("jax")
    if jax is not None:
        from jax._src import xla_bridge

        backend = (
            jax.default_backend()
            if xla_bridge.backends_are_initialized() else "no-backend"
        )
    return pa.table({
        "pid": [os.getpid()],
        "jax_platforms": [os.environ.get("JAX_PLATFORMS", "")],
        "backend": [backend],
    })


def run_train(sizes: dict, platform: str, mesh_axes: dict) -> dict:
    """ETL on the cluster's CPU workers, then the fits of ``sizes`` in
    this process, which holds the devices."""
    import jax
    import numpy as np
    import optax

    import raydp_tpu
    import raydp_tpu.dataframe as rdf
    from raydp_tpu.dataframe import col
    from raydp_tpu.models.transformer import SequenceClassifier, bert_base
    from raydp_tpu.native import lib as native
    from raydp_tpu.parallel import MeshSpec
    from raydp_tpu.train import JAXEstimator
    from raydp_tpu.utils.profiling import metrics

    devices = jax.local_devices()
    assert devices[0].platform == platform, devices
    mesh = MeshSpec(**(mesh_axes or {"dp": len(devices)}))
    assert mesh.size == len(devices), (
        f"mesh {mesh.axis_sizes} does not cover the {len(devices)} devices"
    )
    seq, steps = sizes["seq"], sizes["steps_per_epoch"]
    batch = sizes["per_chip_batch"] * len(devices)
    n_rows = steps * batch
    features = [f"t{i}" for i in range(seq)]
    out: dict = {"mesh": mesh.axis_sizes, "batch": batch, "seq": seq}

    status = native.native_status()
    assert status["native"], (
        "the native gather library did not build; the loader would run "
        "on its numpy twin"
    )
    out["native"] = {
        "path": os.path.relpath(status["path"], ROOT),
        "built_here": status["built_here"],
    }
    log(f"native library: {out['native']}")

    cfg0 = bert_base(**sizes["model"])
    raydp_tpu.init(app_name="chip-smoke-train", num_workers=2)
    try:
        t0 = time.perf_counter()
        raw = rdf.from_pandas(
            _glue_frame(n_rows * 5 // 4, seq, cfg0.vocab_size),
            num_partitions=4,
        )
        # Narrow ops (filter, derived column) fused on the workers, then
        # one exchange.
        df = (
            raw.filter(col("valid") == 1)
            .withColumn("label", col("marker"))
            .select(*features, "label")
            .repartition(4)
        )
        n_out = df.count()
        assert n_out == n_rows, (n_out, n_rows)
        assert "repartition[4]" in df.explain(quiet=True)
        probe = df.mapPartitions(_worker_probe).to_pandas()
        assert (probe["jax_platforms"] == "cpu").all(), probe
        assert probe["backend"].isin(
            ["cpu", "no-backend", "jax-not-imported"]
        ).all(), probe
        assert os.getpid() not in set(probe["pid"]), probe
        out["etl"] = {
            "seconds": round(time.perf_counter() - t0, 2),
            "rows": n_rows,
            "worker_backends": sorted(set(probe["backend"])),
        }
        log(f"etl: {out['etl']}")

        out["fits"] = []
        for epoch_mode, impl, epochs in sizes["fits"]:
            cfg = bert_base(
                **sizes["model"], attention_impl=impl,
                # The Mosaic kernel needs the mesh to run per device.
                mesh=mesh.build() if impl == "flash" else None,
            )
            est = JAXEstimator(
                model=SequenceClassifier(cfg=cfg, num_classes=2),
                optimizer=optax.adamw(2e-5),
                loss="softmax_ce",
                num_epochs=epochs,
                batch_size=batch,
                feature_columns=features,
                label_column="label",
                feature_dtype=np.int32,
                label_dtype=np.int32,
                mesh=mesh,
                seed=0,
                epoch_mode=epoch_mode,
            )
            compile_before = metrics.snapshot().get("counters", {}).get(
                "compile/seconds", 0.0
            )
            history = est.fit_on_df(df, num_shards=2)
            assert est.effective_epoch_mode == epoch_mode
            assert len(history) == epochs
            losses = [h["train_loss"] for h in history]
            assert all(np.isfinite(losses)), losses
            assert int(est._state.step) == epochs * steps, (
                int(est._state.step), epochs * steps
            )
            # Parameters and a batch live on every local device.
            leaves = jax.tree_util.tree_leaves(est._state.params)
            for leaf in leaves:
                assert {s.device for s in leaf.addressable_shards} == set(
                    devices
                ), leaf.sharding
            x = np.zeros((batch, seq), np.int32)
            xd, yd = est._shard_batch(x, np.zeros((batch,), np.int32))
            for arr in (xd, yd):
                shards = arr.addressable_shards
                assert {s.device for s in shards} == set(devices)
                assert all(
                    s.data.shape[0] == batch // mesh.dp for s in shards
                )
            fit = {
                "epoch_mode": epoch_mode,
                "attention_impl": impl,
                "steps": int(est._state.step),
                "params": int(sum(leaf.size for leaf in leaves)),
                "losses": [round(float(v), 4) for v in losses],
                "first_epoch_s": round(history[0]["time_s"], 2),
                "compile_s": round(
                    metrics.snapshot()["counters"].get(
                        "compile/seconds", 0.0
                    ) - compile_before, 2
                ),
            }
            if epochs > 1:
                fit["steady_step_s"] = round(
                    history[-1]["time_s"] / steps, 4
                )
                fit["steady_samples_per_s"] = round(
                    history[-1]["samples_per_sec"], 1
                )
            log(f"fit: {fit}")
            out["fits"].append(fit)
    finally:
        raydp_tpu.stop()
    return out


def check_flash_kernels() -> list:
    """Flash forward and grad, compiled by Mosaic (never interpreted),
    against reference_attention within FLASH_TOLERANCE."""
    import jax
    import jax.numpy as jnp

    from raydp_tpu.ops.attention import reference_attention
    from raydp_tpu.ops.flash_attention import flash_attention

    def f32(x):
        return x.astype(jnp.float32)

    results = []
    for b, s, h, d, causal in FLASH_SHAPES:
        keys = jax.random.split(jax.random.PRNGKey(0), 4)
        q, k, v, g = (
            jax.random.normal(key, (b, s, h, d), jnp.bfloat16)
            for key in keys
        )

        def loss(attn, q, k, v):
            return jnp.sum(f32(attn(q, k, v, causal=causal)) * f32(g))

        fwd = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=causal))
        bwd = jax.jit(jax.grad(
            lambda q, k, v: loss(flash_attention, q, k, v), argnums=(0, 1, 2)
        ))
        t0 = time.perf_counter()
        fwd_c = fwd.lower(q, k, v).compile()
        bwd_c = bwd.lower(q, k, v).compile()
        compile_s = time.perf_counter() - t0
        assert "tpu_custom_call" in fwd_c.as_text(), "forward not Mosaic"
        assert bwd_c.as_text().count("tpu_custom_call") >= 2, (
            "backward is not the forward + backward Mosaic kernels"
        )
        got = (fwd_c(q, k, v),) + tuple(bwd_c(q, k, v))
        want = (reference_attention(f32(q), f32(k), f32(v), causal=causal),)
        want += jax.grad(
            lambda q, k, v: loss(reference_attention, q, k, v),
            argnums=(0, 1, 2),
        )(f32(q), f32(k), f32(v))
        errs = {}
        for name, a, r in zip(("out", "dq", "dk", "dv"), got, want):
            a = f32(a)
            assert a.shape == r.shape and bool(jnp.isfinite(a).all()), name
            rel = float(jnp.max(jnp.abs(a - r)) / jnp.max(jnp.abs(r)))
            assert rel <= FLASH_TOLERANCE, (name, rel, (b, s, h, d, causal))
            errs[name] = round(rel, 5)
        res = {"shape": [b, s, h, d], "causal": causal,
               "compile_s": round(compile_s, 2), "max_err_over_ref_max": errs}
        log(f"flash kernel: {res}")
        results.append(res)
    return results


# ------------------------------------------------------------------ serve

def _decode_engine_factory(model_overrides: dict, platform: str):
    """Zero-arg engine factory, shipped to the replica by value."""
    def factory():
        import jax
        import jax.numpy as jnp

        from raydp_tpu.serve.decode import build_transformer_engine

        dev = jax.devices()[0]
        if dev.platform != platform:
            raise RuntimeError(
                f"serve replica is on platform {dev.platform!r}, "
                f"not {platform!r}"
            )
        # Lands in the replica's log, where the phase reads it back.
        print(REPLICA_TAG + json.dumps({
            "platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices()), "pid": os.getpid(),
        }), flush=True)
        return build_transformer_engine(
            dtype=jnp.bfloat16, **model_overrides
        )

    return factory


def run_serve(sizes: dict, platform: str) -> dict:
    from raydp_tpu.serve import ReplicaGroup

    group = ReplicaGroup(
        replicas=1, mode="decode", label="chip-smoke-serve",
        model_fn=_decode_engine_factory(sizes["serve_model"], platform),
        max_restarts=0,
    )
    t0 = time.perf_counter()
    group.start()
    try:
        wanted = sizes["serve_requests"]
        reqs = [
            group.submit_generate(
                [1 + (i * 7 + j) % 200 for j in range(plen)],
                max_new=n_new, timeout_s=PHASE_TIMEOUT_S["serve"],
            )
            for i, (plen, n_new) in enumerate(wanted)
        ]
        pending = list(reqs)
        while pending:
            pending = [r for r in pending if not r.done.wait(1.0)]
            if group.stats()["dead_lineages"]:
                raise RuntimeError(
                    "the decode replica died (see its log below)"
                )
        answers = []
        for (plen, n_new), req in zip(wanted, reqs):
            res = req.wait()
            assert res["n"] == n_new == len(res["tokens"]), (plen, res)
            assert res["finish_reason"] == "length", res
            assert all(0 <= t < 256 for t in res["tokens"]), res
            answers.append({"prompt_len": plen, "tokens": res["n"],
                            "ttft_s": round(req.ttft_s() or 0.0, 3)})
        stats = group.stats()["decode"]
        log_path = os.path.join(group._log_dir, "replica-0.log")
    finally:
        group.stop()
    with open(log_path) as f:
        tagged = [ln for ln in f if ln.startswith(REPLICA_TAG)]
    assert tagged, f"replica never reported its device ({log_path})"
    replica = json.loads(tagged[-1][len(REPLICA_TAG):])
    assert replica["platform"] == platform, replica
    assert replica["pid"] != os.getpid()
    _assert_no_backend_here("serve")
    out = {
        "replica_device": replica,
        "answers": answers,
        "seconds": round(time.perf_counter() - t0, 2),
        "tokens": stats["tokens"],
    }
    log(f"serve: {out}")
    return out


def _assert_no_backend_here(phase: str) -> None:
    """The phase process must have left the chip to its child."""
    from jax._src import xla_bridge

    assert not xla_bridge.backends_are_initialized(), (
        f"the {phase} driver created a JAX backend; on a one-chip host "
        "that takes the chip from the process that needs it"
    )


# ------------------------------------------------------------------- gang

def _taxi_estimator_factory(platform: str):
    """Estimator factory, shipped to the rank by value; runs inside the
    shipped function after jax.distributed.initialize."""

    def make_estimator():
        import jax
        import optax

        from raydp_tpu.models.mlp import taxi_fare_regressor
        from raydp_tpu.parallel import MeshSpec
        from raydp_tpu.train import JAXEstimator
        from raydp_tpu.train.estimator import TrainingCallback

        dev = jax.devices()[0]
        if dev.platform != platform:
            raise RuntimeError(
                f"gang rank is on platform {dev.platform!r}, "
                f"not {platform!r}"
            )

        class ReportDevice(TrainingCallback):
            def on_epoch_end(self, epoch, metrics):
                metrics["device"] = {
                    "platform": dev.platform, "kind": dev.device_kind,
                    "count": len(jax.devices()), "pid": os.getpid(),
                }

        return JAXEstimator(
            model=taxi_fare_regressor(),
            optimizer=optax.adam(1e-3),
            loss="smooth_l1",
            num_epochs=3,
            batch_size=512,
            feature_columns=["hour", "day_of_week", "distance_km",
                             "passenger_count"],
            label_column="fare_amount",
            mesh=MeshSpec(dp=len(jax.devices())),
            seed=0,
            epoch_mode="stream",
            callbacks=[ReportDevice()],
        )

    return make_estimator


def run_gang(sizes: dict, platform: str) -> dict:
    import numpy as np

    sys.path.insert(0, os.path.join(ROOT, "examples"))
    from data_process import nyc_taxi_preprocess, synthetic_taxi

    import raydp_tpu
    import raydp_tpu.dataframe as rdf
    from raydp_tpu.data import MLDataset
    from raydp_tpu.train.spmd_fit import fit_spmd

    t0 = time.perf_counter()
    raydp_tpu.init(app_name="chip-smoke-gang", num_workers=2)
    try:
        df = nyc_taxi_preprocess(
            rdf.from_pandas(synthetic_taxi(sizes["taxi_rows"]),
                            num_partitions=4)
        )
        ds = MLDataset.from_df(df, num_shards=1)
        fit = fit_spmd(
            _taxi_estimator_factory(platform), ds, world_size=1,
            max_restarts=0, timeout=PHASE_TIMEOUT_S["gang"],
        )
    finally:
        raydp_tpu.stop()
    history = fit["history"]
    losses = [h["train_loss"] for h in history]
    assert len(history) == 3 and all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], losses
    rank = history[-1]["device"]
    assert rank["platform"] == platform, rank
    assert rank["pid"] != os.getpid()
    assert fit["restarts"] == 0 and fit["world_size"] == 1
    _assert_no_backend_here("gang")
    out = {
        "rank_device": rank,
        "losses": [round(float(v), 4) for v in losses],
        "seconds": round(time.perf_counter() - t0, 2),
    }
    log(f"gang: {out}")
    return out


# ----------------------------------------------------------------- driver

def _parse_mesh(text: str) -> dict:
    """``dp=2,tp=2`` -> {"dp": 2, "tp": 2}; empty -> dp over all."""
    return {
        k.strip(): int(v)
        for k, v in (part.split("=") for part in text.split(",") if part)
    }


def run_phase(phase: str, mesh_text: str) -> int:
    """Child entry: one phase in this process; the result goes to stdout
    as one tagged line, everything else to stderr."""
    sys.path.insert(0, ROOT)
    if phase == "train":
        device = require_tpu()
        import jax

        from raydp_tpu.utils.compile_cache import (
            CACHE_DIR_ENV,
            compile_cache_dir,
        )

        cache_dir = os.environ.get(CACHE_DIR_ENV) or compile_cache_dir()
        entries_before = len(glob.glob(os.path.join(cache_dir, "*")))
        result = {
            "device": device,
            "jax": jax.__version__,
            **run_train(FULL, "tpu", _parse_mesh(mesh_text)),
            "flash_kernels": check_flash_kernels(),
        }
        result["compile_cache"] = {
            "dir": cache_dir,
            "entries_before": entries_before,
            "entries_after": len(glob.glob(os.path.join(cache_dir, "*"))),
        }
    elif phase == "serve":
        result = run_serve(FULL, "tpu")
    else:
        result = run_gang(FULL, "tpu")
    print(RESULT_TAG + json.dumps(result), flush=True)
    return 0


def _log_tails(since: float, lines: int = 40) -> str:
    """Tails of the worker, rank and replica logs a phase wrote; they
    live under /tmp/raydp_tpu on a machine that is thrown away."""
    chunks = []
    for path in sorted(
        glob.glob("/tmp/raydp_tpu/**/*.log", recursive=True)
    ):
        if os.path.getmtime(path) < since:
            continue
        with open(path, errors="replace") as f:
            tail = f.readlines()[-lines:]
        chunks.append(f"--- {path}\n{''.join(tail)}")
    return "\n".join(chunks)


def _run_child(phase: str, mesh_text: str):
    """Run one phase in a process group of its own, so that a timeout
    or a crash leaves no worker, rank or replica behind."""
    started = time.time()
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--phase", phase,
         "--mesh", mesh_text],
        stdout=subprocess.PIPE, text=True, cwd=ROOT,
        start_new_session=True,
    )
    try:
        stdout, _ = proc.communicate(timeout=PHASE_TIMEOUT_S[phase])
        failure = None if proc.returncode == 0 else (
            f"exit code {proc.returncode}"
        )
    except subprocess.TimeoutExpired:
        failure = f"no result within {PHASE_TIMEOUT_S[phase]} s"
        stdout = ""
    finally:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.wait()
    result = None
    for line in stdout.splitlines():
        if line.startswith(RESULT_TAG):
            result = json.loads(line[len(RESULT_TAG):])
    if failure is None and result is None:
        failure = "the phase printed no result"
    if failure is not None:
        if proc.returncode != NO_TPU_EXIT:
            tails = _log_tails(started)
            if tails:
                print(tails, file=sys.stderr, flush=True)
        log(f"phase {phase} FAILED: {failure}")
        return None
    return result


def main(argv) -> int:
    if not os.path.isdir(os.path.join(ROOT, "raydp_tpu")):
        print(
            "chip_smoke: the raydp_tpu package is not next to "
            "chip_smoke.py; run it from a checkout of the repository",
            file=sys.stderr,
        )
        return 2
    mesh_text = argv[argv.index("--mesh") + 1] if "--mesh" in argv else ""
    if "--phase" in argv:
        phase = argv[argv.index("--phase") + 1]
        if phase not in PHASES:
            print(f"chip_smoke: unknown phase {phase!r}", file=sys.stderr)
            return 2
        return run_phase(phase, mesh_text)

    t0 = time.perf_counter()
    results = {}
    for phase in PHASES:
        log(f"phase {phase} ...")
        result = _run_child(phase, mesh_text)
        if result is None:
            return 1
        results[phase] = result
    for line in result_lines(results, time.perf_counter() - t0):
        print(line, flush=True)
    return 0


def result_lines(results: dict, seconds: float) -> list:
    """The two stdout lines of a passed run: the summary of what was
    printed-not-judged, then the verdict, which is the LAST line and has
    exactly the keys the driver reads."""
    train = dict(results["train"])
    device = train.pop("device")
    summary = {
        "jax": train.pop("jax"),
        "seconds": round(seconds, 1),
        "phases": {**results, "train": train},
        "claim": None,
    }
    verdict = {
        "ok": True,
        "device": {
            "platform": str(device["platform"]),
            "kind": str(device["kind"]),
            "count": int(device["count"]),
        },
    }
    return [json.dumps(summary), json.dumps(verdict)]


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
