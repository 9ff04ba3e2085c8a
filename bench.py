"""Benchmark harness: end-to-end training throughput on real hardware.

Prints ONE JSON line. Headline keys ({"metric", "value", "unit",
"vs_baseline"}) carry the NYC-taxi config for round-over-round
comparability; the ``configs`` map carries the full BASELINE.md matrix —
taxi MLP, titanic classifier, BERT-GLUE fine-tune, DLRM/Criteo — each
with samples/s, achieved model-FLOPs utilisation (``mfu``), and a
baseline ratio, plus the device-ingest bandwidth config (``gb_per_sec``).

The reference publishes no numbers (BASELINE.md), so every baseline is
measured here: the reference's own mechanism class — torch CPU
DataLoader + per-batch step on an equivalent model (reference:
examples/pytorch_nyctaxi.py, TorchEstimator train_epoch,
python/raydp/torch/estimator.py:227-248) — versus this framework's
DataFrame/MLDataset → JAXEstimator path on the visible accelerator.

One process runs the whole matrix on JAX's default backend, and every
result is stamped with the platform, ``device_kind`` and device count it
ran on. ``JAX_PLATFORMS=cpu`` in the environment asks for a CPU run, at
reduced sizes; without it the run needs a TPU, and finding none is exit
code 2 and no numbers. A section that raises is recorded as
``{"error": ...}`` and the others still run, but the run then exits 1.

Emission guarantees:

* Every completed config is immediately persisted to
  ``BENCH_partial.json`` next to this file (override with
  ``RAYDP_TPU_BENCH_PARTIAL``).
* SIGTERM/SIGINT handlers and an ``atexit`` hook print the final JSON
  line from whatever has completed, so even a driver-timeout kill
  (rc=124) yields a parseable result with ``"partial": true``.

Env knobs: ``RAYDP_TPU_BENCH_BUDGET_S`` (self-deadline, default 2700),
``RAYDP_TPU_ONLY=a,b`` (restrict the matrix to the named configs).
"""
from __future__ import annotations

import atexit
import json
import os
import signal
import sys
import tempfile
import threading
import time

import numpy as np

# Set by main() when the run was asked onto the CPU (JAX_PLATFORMS=cpu):
# configs shrink so the matrix still completes in minutes.
_ON_CPU = False

# Soft wall-clock deadline (time.monotonic value) consulted by the
# long multi-combo benches (sweeps, seq-scaling) so a single config
# cannot eat the whole bench window. None = no deadline.
_DEADLINE = None


def _over_deadline(margin: float = 0.0) -> bool:
    return _DEADLINE is not None and time.monotonic() > _DEADLINE - margin


def _only_filter(names):
    """Operator knob: ``RAYDP_TPU_ONLY=a,b`` restricts a matrix to the
    named configs — re-validating one config after a fix without paying
    for the whole matrix."""
    only = os.environ.get("RAYDP_TPU_ONLY")
    if not only:
        return list(names)
    wanted = {n.strip() for n in only.split(",") if n.strip()}
    return [n for n in names if n in wanted]

# bf16 peak FLOP/s per chip by device kind (public spec sheets).
PEAK_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5": 459e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}


def _peak_flops():
    import jax

    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return None  # MFU not meaningful
    for name, peak in PEAK_FLOPS.items():
        if dev.device_kind.startswith(name):
            return peak
    raise ValueError(
        f"no peak FLOP/s entry for device_kind {dev.device_kind!r} "
        f"(platform {dev.platform!r}); add it to PEAK_FLOPS"
    )


def _mfu(samples_per_sec, flops_per_sample):
    peak = _peak_flops()
    if peak is None or not samples_per_sec:
        return None
    return round(samples_per_sec * flops_per_sample / peak, 4)


def _param_count(params) -> int:
    import jax

    return sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(params))


def _timed_train_steps(loss_of_params, params, tx, batch, n_steps=6):
    """Shared raw-train-step timing harness (sweep/study benches):
    jit a value_and_grad + optax update step, run one compile/warmup
    step, then time ``n_steps`` bracketed by host fetches of the loss.
    Returns elapsed seconds for the timed steps."""
    import jax
    import optax

    opt_state = tx.init(params)

    @jax.jit
    def step(params, opt_state, *args):
        loss, grads = jax.value_and_grad(loss_of_params)(params, *args)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    # Both brackets end with a host fetch of the loss: float() has to
    # materialize the value, which forces the whole step chain. On a
    # local TPU block_until_ready waits for the device just as well (PR
    # 21 chip run: a 44 TFLOP matmul chain dispatched in 0.3 ms and
    # block_until_ready returned after 237 ms, 186 TFLOP/s); the fetch
    # stays because it costs one scalar and needs no such assumption.
    params, opt_state, loss = step(params, opt_state, *batch)
    float(loss)
    t0 = time.perf_counter()
    for _ in range(n_steps):
        params, opt_state, loss = step(params, opt_state, *batch)
    float(loss)
    return time.perf_counter() - t0


def _steady(history):
    """samples/s over steady-state epochs (epoch 0 pays XLA compile)."""
    steady = history[1:] or history
    return sum(e["samples_per_sec"] for e in steady) / len(steady)


def _best_of_2_fit(est, ds):
    """Best-of-2 steady rate. Single-run rates swing ±10% on shared
    hosts. fit() returns the estimator's CUMULATIVE history (the same
    list object), so run 1 is snapshotted and run 2 sliced to its own
    epochs; _steady then drops each run's first epoch (run 2 re-jits
    too)."""
    h1 = list(est.fit(ds))
    h2 = est.fit(ds)[len(h1):]
    return max(_steady(h1), _steady(h2))


def _torch_rate(model, make_batch, n_batches=4, loss="mse", budget_s=None):
    """Steady samples/s of a torch CPU train loop (reference mechanism
    class); first batch is warmup. ``budget_s`` caps wall time: once at
    least one timed batch exists, the loop stops instead of running the
    full count — a full-size model on a starved host can take minutes
    per batch, and a single multi-minute batch is already a low-noise
    per-sample rate."""
    import torch

    opt = torch.optim.Adam(model.parameters(), lr=1e-3)
    loss_fn = (
        torch.nn.MSELoss() if loss == "mse" else torch.nn.CrossEntropyLoss()
    )
    rates = []
    t_start = time.perf_counter()
    for i in range(n_batches):
        if rates and (
            (budget_s is not None
             and time.perf_counter() - t_start > budget_s)
            or _over_deadline(margin=120.0)
        ):
            break
        xb, yb = make_batch(i)
        t0 = time.perf_counter()
        opt.zero_grad()
        out = model(xb)
        loss_val = loss_fn(out, yb)
        loss_val.backward()
        opt.step()
        dt = time.perf_counter() - t0
        if i > 0:
            rates.append(len(yb) / dt)
    return sum(rates) / len(rates)


# ----------------------------------------------------------- taxi MLP

def bench_nyctaxi():
    import pandas as pd

    from raydp_tpu.models.mlp import taxi_fare_regressor
    from raydp_tpu.train.estimator import JAXEstimator

    n_rows, n_feat, batch = 120_000, 14, 512
    if _ON_CPU:
        n_rows = 20_000
    rs = np.random.RandomState(42)
    x = rs.rand(n_rows, n_feat).astype(np.float32)
    w = rs.rand(n_feat, 1).astype(np.float32)
    y = (x @ w + 0.1 * rs.randn(n_rows, 1)).astype(np.float32)

    cols = [f"f{i}" for i in range(n_feat)]
    df = pd.DataFrame(x, columns=cols)
    df["label"] = y
    est = JAXEstimator(
        model=taxi_fare_regressor(),
        loss="mse",
        num_epochs=3,
        batch_size=batch,
        feature_columns=cols,
        label_column="label",
        shuffle=True,
    )
    ours = _steady(est.fit_on_df(df))
    n_params = _param_count(est._state.params)

    import torch

    t_model = torch.nn.Sequential(
        torch.nn.Linear(n_feat, 256), torch.nn.ReLU(),
        torch.nn.Linear(256, 128), torch.nn.ReLU(),
        torch.nn.Linear(128, 64), torch.nn.ReLU(),
        torch.nn.Linear(64, 32), torch.nn.ReLU(),
        torch.nn.Linear(32, 1),
    )
    xt, yt = torch.from_numpy(x), torch.from_numpy(y)

    def make_batch(i):
        lo = (i * batch) % (n_rows - batch)
        return xt[lo:lo + batch], yt[lo:lo + batch]

    base = _torch_rate(t_model, make_batch, n_batches=6)
    return {
        "samples_per_sec": round(ours, 1),
        "unit": "samples/s",
        "vs_baseline": round(ours / base, 3),
        "mfu": _mfu(ours, 6 * n_params),
        "baseline": "torch-cpu per-batch DDP-style loop",
    }


# ----------------------------------------------------------- titanic

def bench_titanic():
    import pandas as pd

    from raydp_tpu.models.mlp import binary_classifier
    from raydp_tpu.train.estimator import JAXEstimator

    n_rows, n_feat, batch = 16_384, 8, 256
    rs = np.random.RandomState(7)
    x = rs.rand(n_rows, n_feat).astype(np.float32)
    logit = x @ rs.randn(n_feat).astype(np.float32) - x.mean(axis=1)
    y = (logit + 0.3 * rs.randn(n_rows) > 0).astype(np.float32)

    cols = [f"f{i}" for i in range(n_feat)]
    df = pd.DataFrame(x, columns=cols)
    df["survived"] = y
    est = JAXEstimator(
        model=binary_classifier(),
        loss="bce",
        metrics=["accuracy"],
        num_epochs=3,
        batch_size=batch,
        feature_columns=cols,
        label_column="survived",
    )
    ours = _steady(est.fit_on_df(df))
    n_params = _param_count(est._state.params)

    import torch

    t_model = torch.nn.Sequential(
        torch.nn.Linear(n_feat, 128), torch.nn.ReLU(),
        torch.nn.Linear(128, 64), torch.nn.ReLU(),
        torch.nn.Linear(64, 1),
    )
    xt = torch.from_numpy(x)
    yt = torch.from_numpy(y.reshape(-1, 1))

    def make_batch(i):
        lo = (i * batch) % (n_rows - batch)
        return xt[lo:lo + batch], yt[lo:lo + batch]

    base = _torch_rate(t_model, make_batch, n_batches=6)
    return {
        "samples_per_sec": round(ours, 1),
        "unit": "samples/s",
        "vs_baseline": round(ours / base, 3),
        "mfu": _mfu(ours, 6 * n_params),
        "baseline": "torch-cpu per-batch loop",
    }


# ----------------------------------------------------------- BERT-GLUE

BERT_SEQ = 128
BERT_BATCH = 32


def _bert_sweep(make_cfg, batches=(32, 64, 128), impls=("dense", "flash"),
                include_remat=True, skip=()):
    """Raw train-step throughput over (batch, attention impl, remat):
    the MFU levers the r2 verdict asked to sweep.
    Remat variants run at the largest batch only — that is where
    memory-bound configs need the FLOPs-for-HBM trade. ``skip`` holds
    combo tags already measured elsewhere (the pre-fit impl probe) so
    they are not paid twice. Returns (table, best_batch,
    best_impl_config)."""
    import jax
    import jax.numpy as jnp
    import optax

    from raydp_tpu.models.transformer import SequenceClassifier

    rs = np.random.RandomState(0)
    table = {}
    best = (None, None, 0.0)
    combos = [(impl, False, b) for impl in impls for b in batches]
    if include_remat:
        combos += [(impl, True, max(batches)) for impl in impls]
    combos = [
        (impl, remat, b) for impl, remat, b in combos
        if f"{impl}{'_remat' if remat else ''}_b{b}" not in skip
    ]
    for impl, remat, batch in combos:
        cfg = make_cfg(impl, remat)
        model = SequenceClassifier(cfg=cfg, num_classes=2)
        ids = jnp.asarray(
            rs.randint(0, cfg.vocab_size, size=(batch, BERT_SEQ))
        )
        labels = jnp.asarray(rs.randint(0, 2, size=(batch,)))

        def loss_fn(p, ids, labels):
            logits = model.apply(p, ids)
            ll = jax.nn.log_softmax(logits.astype(jnp.float32))
            return -jnp.mean(
                jnp.take_along_axis(ll, labels[:, None], axis=-1)
            )

        tag = f"{impl}{'_remat' if remat else ''}_b{batch}"
        if _over_deadline(margin=90.0):
            table[tag] = "skipped (bench deadline)"
            continue
        try:
            # Jitted init: un-jitted flax init dispatches hundreds of
            # small ops individually instead of one compiled program.
            params = jax.jit(model.init)(
                jax.random.key(0, impl="rbg"), ids
            )
            n_steps = 6
            dt = _timed_train_steps(
                loss_fn, params, optax.adamw(2e-5), (ids, labels),
                n_steps=n_steps,
            )
            rate = n_steps * batch / dt
            table[tag] = round(rate, 2)
            if rate > best[2]:
                best = (batch, (impl, remat), rate)
        except Exception as exc:
            table[tag] = f"{type(exc).__name__}: {str(exc)[:80]}"
        params = None
    return table, best[0], best[1]


def bench_bert():
    import optax
    import pyarrow as pa

    from raydp_tpu.data.ml_dataset import MLDataset
    from raydp_tpu.models.transformer import SequenceClassifier, bert_base
    from raydp_tpu.train.estimator import JAXEstimator

    sweep = None
    bert_batch = BERT_BATCH
    if _ON_CPU:
        import jax.numpy as jnp

        from raydp_tpu.models.transformer import tiny_transformer

        # f32 on CPU: XLA CPU has no fast bf16 kernels — the bf16 cast
        # chain nearly halves throughput (measured 62 -> 111 samples/s).
        # On chip bf16 is the MXU-native dtype and stays the default.
        cfg = tiny_transformer(
            max_len=BERT_SEQ, dropout_rate=0.1, dtype=jnp.float32
        )
    else:
        # On chip the FIT comes first-ish — it carries the headline
        # samples/s + MFU the round is judged on; the full sweep runs
        # after with whatever budget remains (r4 lesson: the 8-combo
        # sweep-first burned the whole chip window in compiles and
        # the fit never ran). Batch 128 over batch 32:
        # bigger per-step GEMMs are strictly better for MXU utilisation
        # at seq 128. The one lever worth 2 compiles up front is the
        # attention impl — a 2-combo probe picks dense vs flash for the
        # fit instead of guessing (deadline-guarded like the sweep).
        bert_batch = 128
        impl = "dense"
        probe, _, probe_best = _bert_sweep(
            lambda i, r: bert_base(
                max_len=BERT_SEQ, dropout_rate=0.1, attention_impl=i,
                remat=r,
            ),
            batches=(bert_batch,),
            include_remat=False,
        )
        if probe_best is not None:
            impl = probe_best[0]
        cfg = bert_base(
            max_len=BERT_SEQ, dropout_rate=0.1, attention_impl=impl
        )
    if _over_deadline(margin=120.0):
        out = {"skipped": "bench deadline before estimator fit"}
        if not _ON_CPU:
            # Don't throw away the paid-for pre-fit probe table.
            out["batch_sweep_samples_per_sec"] = probe
        return out
    model = SequenceClassifier(cfg=cfg, num_classes=2)
    n_rows = 20 * bert_batch
    bert_epochs = 7 if _ON_CPU else 3  # more steady epochs vs noise
    rs = np.random.RandomState(0)
    ids = rs.randint(0, cfg.vocab_size, size=(n_rows, BERT_SEQ)).astype(
        np.int32
    )
    labels = rs.randint(0, 2, size=(n_rows,)).astype(np.int32)
    table = pa.table(
        {**{f"t{i}": ids[:, i] for i in range(BERT_SEQ)}, "label": labels}
    )
    ds = MLDataset([table], num_shards=1)
    est = JAXEstimator(
        model=model,
        optimizer=optax.adamw(2e-5),
        loss="softmax_ce",
        num_epochs=bert_epochs,
        batch_size=bert_batch,
        feature_columns=[f"t{i}" for i in range(BERT_SEQ)],
        label_column="label",
        feature_dtype=np.int32,
        label_dtype=np.int32,
        shuffle=False,
        # One dispatch per epoch (dataset is small enough to live on
        # device): measured +7% over the streaming loop on CPU, and on
        # chip it removes every per-step host dispatch.
        epoch_mode="scan",
    )
    ours = _best_of_2_fit(est, ds)
    n_params = _param_count(est._state.params)
    # Train FLOPs/sample ≈ 3 × forward; forward = 2·N·S (param matmuls)
    # + 4·L·S²·d (attention scores + values).
    fwd = 2 * n_params * BERT_SEQ + 4 * cfg.n_layers * BERT_SEQ**2 * cfg.d_model
    flops_per_sample = 3 * fwd

    if _ON_CPU:
        # Tiny model: batches are sub-second, so run-to-run noise is the
        # enemy — take the better of two full measurements.
        base = max(_bert_torch_baseline(cfg), _bert_torch_baseline(cfg))
    else:
        # Full-size bert-base through torch on this host runs MINUTES
        # per batch (~10.8 TFLOPs fwd+bwd at batch 128 on one core); the
        # r4 chip run burned its whole remaining window inside the
        # max-of-two full-batch baselines and the already-measured fit
        # number was never recorded. Per-sample CPU throughput is ~flat
        # in batch at seq 128 (the encoder GEMMs saturate the core
        # either way), so time a reduced batch once, under a hard cap.
        base = _bert_torch_baseline(
            cfg, batch=8, n_batches=3, budget_s=150.0
        )
    if not _ON_CPU:
        # The estimator's bert-base state (params + adamw moments + the
        # scan-mode device-resident dataset) is dead weight now; free
        # the HBM before the sweep inits its own full models.
        est = None
    if not _ON_CPU and not _over_deadline(margin=180.0):
        # Post-fit sweep with leftover budget only — the MFU-lever table
        # the r2 verdict asked for, trimmed by default to remat at the
        # fit batch (the impl probe above covered the non-remat combos).
        # RAYDP_TPU_FULL_SWEEP=1 restores the full grid.
        full = os.environ.get("RAYDP_TPU_FULL_SWEEP") == "1"
        sweep, _, _ = _bert_sweep(
            lambda impl, remat: bert_base(
                max_len=BERT_SEQ, dropout_rate=0.1, attention_impl=impl,
                remat=remat,
            ),
            batches=(32, 64, 128) if full else (bert_batch,),
            skip=set(probe),
        )
        sweep = {**probe, **sweep}
    elif not _ON_CPU:
        sweep = probe
    out = {
        "samples_per_sec": round(ours, 2),
        "unit": "samples/s",
        "vs_baseline": round(ours / base, 3) if base else None,
        "mfu": _mfu(ours, flops_per_sample),
        "params": n_params,
        "seq_len": BERT_SEQ,
        "batch": bert_batch,
        "attention_impl": cfg.attention_impl,
        "baseline": "torch-cpu TransformerEncoder loop (same model: gelu, "
                    "pos-emb, pooler)",
    }
    if _ON_CPU:
        out["host_cpus"] = os.cpu_count()
        out["note"] = (
            "CPU run: equal models through XLA-CPU vs torch+MKL "
            "measure ~parity (both ~28 GFLOP/s on one core; ratio noise "
            "±7%). The accelerator path is the real comparison — see the "
            "chip section (r1: 16x this baseline at 38% MFU)."
        )
    if sweep is not None:
        out["batch_sweep_samples_per_sec"] = sweep
    return out


def _bert_torch_baseline(cfg, batch=None, n_batches=8, budget_s=None):
    import torch

    batch = BERT_BATCH if batch is None else batch

    class TorchBert(torch.nn.Module):
        """Mirrors the jax SequenceClassifier exactly: token + position
        embeddings with dropout, gelu encoder blocks, tanh pooler, head
        — an equal-compute baseline, not a conveniently thinner one."""

        def __init__(self):
            super().__init__()
            self.emb = torch.nn.Embedding(cfg.vocab_size, cfg.d_model)
            self.pos = torch.nn.Embedding(cfg.max_len, cfg.d_model)
            self.drop = torch.nn.Dropout(cfg.dropout_rate)
            layer = torch.nn.TransformerEncoderLayer(
                d_model=cfg.d_model, nhead=cfg.n_heads,
                dim_feedforward=cfg.d_ff, batch_first=True,
                dropout=cfg.dropout_rate,
                activation="gelu",  # BERT's activation, like the jax model
            )
            self.enc = torch.nn.TransformerEncoder(layer, cfg.n_layers)
            self.pooler = torch.nn.Linear(cfg.d_model, cfg.d_model)
            self.head = torch.nn.Linear(cfg.d_model, 2)

        def forward(self, ids):
            pos = torch.arange(ids.shape[1], device=ids.device)[None, :]
            h = self.drop(self.emb(ids) + self.pos(pos))
            h = self.enc(h)
            pooled = torch.tanh(self.pooler(h[:, 0]))
            return self.head(pooled)

    model = TorchBert()
    rs = np.random.RandomState(1)

    def make_batch(i):
        ids = torch.from_numpy(
            rs.randint(0, cfg.vocab_size, size=(batch, BERT_SEQ))
        )
        y = torch.from_numpy(rs.randint(0, 2, size=(batch,)))
        return ids, y

    # 8 batches (7 timed) by default: at ~0.3 s/batch, two timed batches
    # swung the baseline ±30% run-to-run — the ratio was measuring noise.
    return _torch_rate(
        model, make_batch, n_batches=n_batches, loss="ce",
        budget_s=budget_s,
    )


# ----------------------------------------------------------- DLRM

DLRM_BATCH = 4096
DLRM_VOCABS = tuple([1_000_000] * 2 + [100_000] * 6 + [10_000] * 18)


def bench_dlrm():
    import optax
    import pyarrow as pa

    from raydp_tpu.data.ml_dataset import MLDataset
    from raydp_tpu.models.dlrm import DLRMConfig, PackedDLRM
    from raydp_tpu.train.estimator import JAXEstimator

    import jax.numpy as jnp

    vocabs = (
        tuple([10_000] * 4 + [1_000] * 8) if _ON_CPU else DLRM_VOCABS
    )
    # f32 on CPU: XLA CPU has no fast bf16 kernels (~20%
    # slower than f32 measured); on chip bf16 is the MXU-native dtype.
    cfg = DLRMConfig(vocab_sizes=vocabs, embed_dim=64,
                     bottom_mlp=(512, 256, 64),
                     top_mlp=(1024, 512),
                     dtype=jnp.float32 if _ON_CPU else jnp.bfloat16)
    n_rows = (8 if _ON_CPU else 16) * DLRM_BATCH
    rs = np.random.RandomState(3)
    dense = rs.rand(n_rows, cfg.dense_features).astype(np.float32)
    sparse = np.stack(
        [rs.randint(0, v, size=n_rows) for v in cfg.vocab_sizes], axis=1
    ).astype(np.int32)
    y = (rs.rand(n_rows) < 0.25).astype(np.float32)

    dense_cols = [f"d{i}" for i in range(cfg.dense_features)]
    sparse_cols = [f"c{i}" for i in range(cfg.n_tables)]
    table = pa.table(
        {
            **{c: dense[:, i] for i, c in enumerate(dense_cols)},
            **{c: sparse[:, i] for i, c in enumerate(sparse_cols)},
            "click": y,
        }
    )
    ds = MLDataset([table], num_shards=1)
    est = JAXEstimator(
        model=PackedDLRM(cfg=cfg),
        optimizer=optax.adagrad(1e-2),
        loss="bce",
        num_epochs=3,
        batch_size=DLRM_BATCH,
        feature_columns=dense_cols + sparse_cols,
        label_column="click",
        shuffle=False,
        # Scan mode: the whole epoch is ONE dispatch (lax.scan over
        # device-resident batches) — ~19% over the streaming loop in the
        # CPU measurement, and the MXU keeps its pipeline full
        # on chip. Ids survive the float32 feature pack exactly: every
        # vocab here is < 2^24.
        epoch_mode="scan",
    )
    ours = _best_of_2_fit(est, ds)
    # MFU over the dense-matmul FLOPs (embedding lookups are
    # bandwidth-bound, not MXU work).
    import jax.tree_util as jtu

    mlp_params = sum(
        int(np.prod(x.shape))
        for p, x in jtu.tree_leaves_with_path(est._state.params)
        if "emb_" not in jtu.keystr(p)
    )
    if _ON_CPU:
        base = max(_dlrm_torch_baseline(cfg), _dlrm_torch_baseline(cfg))
    else:
        # One budget-capped run at full size: the chip host pays for
        # this on a single starved core, and a slow-batch measurement is
        # already low-noise (same rationale as the BERT chip baseline).
        base = _dlrm_torch_baseline(cfg, budget_s=150.0)
    return {
        "samples_per_sec": round(ours, 1),
        "unit": "samples/s",
        "vs_baseline": round(ours / base, 3) if base else None,
        "mfu": _mfu(ours, 6 * mlp_params),
        "tables": len(cfg.vocab_sizes),
        # What actually ran: a multi-process fit silently streams even
        # with scan requested — recorded so round-over-round numbers
        # aren't compared across different execution modes.
        "epoch_mode": getattr(est, "effective_epoch_mode", None),
        "baseline": "torch-cpu EmbeddingBag DLRM loop",
    }


def _dlrm_torch_baseline(cfg, budget_s=None):
    import torch

    class TorchDLRM(torch.nn.Module):
        """Mirrors the jax config EXACTLY (same bottom/top widths) — an
        equal-FLOPs baseline, not a conveniently smaller one."""

        def __init__(self):
            super().__init__()
            self.embs = torch.nn.ModuleList(
                [torch.nn.Embedding(v, cfg.embed_dim) for v in cfg.vocab_sizes]
            )
            bottom = []
            prev = cfg.dense_features
            for w in cfg.bottom_mlp:
                bottom += [torch.nn.Linear(prev, w), torch.nn.ReLU()]
                prev = w
            self.bottom = torch.nn.Sequential(*bottom)
            n_feats = 1 + len(cfg.vocab_sizes)
            inter = n_feats * (n_feats - 1) // 2
            top = []
            prev = cfg.embed_dim + inter
            for w in cfg.top_mlp:
                top += [torch.nn.Linear(prev, w), torch.nn.ReLU()]
                prev = w
            top.append(torch.nn.Linear(prev, 1))
            self.top = torch.nn.Sequential(*top)

        def forward(self, dense, sparse):
            x = self.bottom(dense)
            feats = torch.stack(
                [x] + [e(sparse[:, i]) for i, e in enumerate(self.embs)],
                dim=1,
            )
            z = torch.bmm(feats, feats.transpose(1, 2))
            iu = torch.triu_indices(z.shape[1], z.shape[2], offset=1)
            inter = z[:, iu[0], iu[1]]
            return self.top(torch.cat([x, inter], dim=1))

    model = TorchDLRM()
    rs = np.random.RandomState(4)
    import torch as _t

    class Wrapper(_t.nn.Module):
        def __init__(self, m):
            super().__init__()
            self.m = m

        def forward(self, pair):
            return self.m(*pair)

    def make_batch(i):
        dense = _t.from_numpy(
            rs.rand(DLRM_BATCH, cfg.dense_features).astype(np.float32)
        )
        sparse = _t.from_numpy(
            np.stack(
                [rs.randint(0, v, size=DLRM_BATCH) for v in cfg.vocab_sizes],
                axis=1,
            )
        )
        y = _t.from_numpy(
            (rs.rand(DLRM_BATCH) < 0.25).astype(np.float32).reshape(-1, 1)
        )
        return (dense, sparse), y

    # 6 batches (5 timed): at ~0.3 s/step two timed batches was pure
    # noise; the mean of five stabilizes the denominator of vs_baseline.
    return _torch_rate(
        Wrapper(model), make_batch, n_batches=6, budget_s=budget_s
    )


# ----------------------------------------------------------- ingest GB/s

def bench_ingest():
    import jax
    import pyarrow as pa

    from raydp_tpu.data.ml_dataset import MLDataset

    n_rows, n_feat, batch = 2_000_000, 16, 65_536
    if _ON_CPU:
        n_rows = 500_000
    rs = np.random.RandomState(5)
    cols = {f"f{i}": rs.rand(n_rows).astype(np.float32) for i in range(n_feat)}
    cols["y"] = rs.rand(n_rows).astype(np.float32)
    table = pa.table(cols)
    ds = MLDataset([table], num_shards=1)

    def timed_epoch(transfer_coalesce):
        loader = ds.to_jax(
            feature_columns=[f"f{i}" for i in range(n_feat)],
            label_column="y",
            batch_size=batch,
            shuffle=True,
            prefetch=4,
            device=jax.devices()[0],
            transfer_coalesce=transfer_coalesce,
        )
        total = 0
        # warm epoch (buffers, compile-free) then timed epoch
        for _ in loader:
            pass
        t0 = time.perf_counter()
        last = None
        for x, yv in loader:
            total += x.nbytes + yv.nbytes
            last = x
        # End on a host fetch of the last batch (see
        # _timed_train_steps); one batch back over the link is noise.
        jax.device_get(last)
        return total / (time.perf_counter() - t0) / 1e9

    # Both transfer modes (r4 verdict #3): per-batch device_puts pay a
    # device-link round trip per batch; coalesced mode amortizes it over
    # ~128MB chunks (RAYDP_TRANSFER_CHUNK_MB) with a multi-chunk
    # in-flight window, features+labels packed into one transfer each.
    micro = timed_epoch(1)
    ours = timed_epoch(None)  # auto-coalesced — the default path

    import torch
    from torch.utils.data import DataLoader, TensorDataset

    x_t = torch.from_numpy(
        np.stack([cols[f"f{i}"] for i in range(n_feat)], axis=1)
    )
    y_t = torch.from_numpy(cols["y"])
    tl = DataLoader(TensorDataset(x_t, y_t), batch_size=batch, shuffle=True)
    t0 = time.perf_counter()
    tb = 0
    for xb, yb in tl:
        tb += xb.numpy().nbytes + yb.numpy().nbytes
    dt = time.perf_counter() - t0
    base = tb / dt / 1e9

    # Fit-path ingest: a near-zero-FLOP model makes fit() wall time
    # infeed-bound, so steady samples/s × bytes/sample measures the
    # estimator's double-buffered sharded device_put pipeline
    # (train/estimator.py _sharded_prefetch) — not just the raw loader.
    import flax.linen as nn
    import optax

    from raydp_tpu.train.estimator import JAXEstimator

    class _Linear(nn.Module):
        @nn.compact
        def __call__(self, x):
            return nn.Dense(1)(x)

    est = JAXEstimator(
        model=_Linear(),
        optimizer=optax.sgd(1e-3),
        loss="mse",
        num_epochs=3,
        batch_size=batch,
        feature_columns=[f"f{i}" for i in range(n_feat)],
        label_column="y",
        shuffle=True,
        epoch_mode="stream",
    )
    fit_rate = _steady(est.fit(ds))
    bytes_per_sample = (n_feat + 1) * 4
    fit_gb = fit_rate * bytes_per_sample / 1e9

    return {
        "gb_per_sec": round(ours, 3),
        "micro_batch_gb_per_sec": round(micro, 3),
        "fit_path_gb_per_sec": round(fit_gb, 3),
        "unit": "GB/s",
        "vs_baseline": round(ours / base, 3),
        "baseline": "torch DataLoader shuffle epoch (host only)",
    }


# ----------------------------------------------------------- ETL shuffle

def _cluster_aggregate(session, wait_s: float = 6.0):
    """Pull the heartbeat-merged cluster aggregate, polling briefly: the
    timed loop just saturated the host, so the workers' last deltas may
    still be a beat (2s) away from the master."""
    deadline = time.monotonic() + wait_s
    while True:
        agg = session.cluster.metrics_snapshot().get("aggregate")
        if agg or time.monotonic() >= deadline:
            return agg
        time.sleep(0.5)


def bench_etl_groupby():
    """Distributed groupBy/agg throughput on the multi-process cluster
    (ETL is the reference's core business; the shuffle rides the native
    hash partitioner)."""
    import pandas as pd

    import raydp_tpu
    import raydp_tpu.dataframe as rdf

    # ETL never touches the device: always run at full size, even when
    # the model configs are at the reduced CPU sizes.
    n_rows = 2_000_000
    rng = np.random.RandomState(9)
    pdf = pd.DataFrame(
        {
            "k": rng.randint(0, 10_000, n_rows),
            "v": rng.randn(n_rows),
            "w": rng.randn(n_rows),
        }
    )
    session = raydp_tpu.init(app_name="bench-etl", num_workers=4)
    try:
        df = rdf.from_pandas(pdf, num_partitions=8)
        # warm (page cache, worker pools)
        df.groupBy("k").agg({"v": "sum"}).count()
        dt = float("inf")
        for _ in range(3):  # best-of-3: single-run noise on shared hosts
            t0 = time.perf_counter()
            out = (
                df.groupBy("k")
                .agg({"v": "sum"}, ("v", "mean"), ("w", "max"))
                .to_pandas()
            )
            dt = min(dt, time.perf_counter() - t0)
        assert len(out) == pdf["k"].nunique()
        ours = n_rows / dt
        # Per-worker view merged from heartbeat-shipped deltas: shows how
        # evenly the shuffle spread over the 4 workers.
        cluster_agg = _cluster_aggregate(session)
    finally:
        raydp_tpu.stop()

    db = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        pdf.groupby("k").agg({"v": ["sum", "mean"], "w": "max"})
        db = min(db, time.perf_counter() - t0)
    base = n_rows / db
    import os

    return {
        "rows_per_sec": round(ours, 1),
        "unit": "rows/s",
        "vs_baseline": round(ours / base, 3),
        "host_cpus": os.cpu_count(),
        "cluster_telemetry": cluster_agg,
        "baseline": "single-process pandas groupby.agg (in-memory)",
    }


def bench_dlrm_embedding_study():
    """take vs one-hot embedding lookup across vocab sizes — the
    measurement behind models/dlrm.py AUTO_ONEHOT_THRESHOLD. Times a
    full train step (lookup + pooled loss + grad update) per impl per
    vocab and reports the measured crossover."""
    import jax
    import jax.numpy as jnp
    import optax

    from raydp_tpu.models.dlrm import AUTO_ONEHOT_THRESHOLD, ShardedEmbedding

    vocabs = (
        [1024, 4096, 8192, 16384]
        if _ON_CPU
        else [1024, 4096, 8192, 32768, 131072]
    )
    batch = 1024 if _ON_CPU else 8192
    embed_dim = 64
    steps = 8
    rs = np.random.RandomState(0)
    results = {}
    for vocab in vocabs:
        if _over_deadline(margin=60.0):
            results[vocab] = {"skipped": "bench deadline"}
            continue
        per_impl = {}
        for impl in ("take", "onehot"):
            model = ShardedEmbedding(
                vocab_size=vocab, embed_dim=embed_dim, impl=impl
            )
            ids = jnp.asarray(rs.randint(0, vocab, size=batch))

            def loss_fn(p, ids):
                emb = model.apply(p, ids)
                return jnp.mean(jnp.square(emb.astype(jnp.float32)))

            params = model.init(jax.random.PRNGKey(0), ids)
            dt = _timed_train_steps(
                loss_fn, params, optax.adagrad(1e-2), (ids,), n_steps=steps
            )
            per_impl[impl] = round(steps * batch / dt, 1)
        results[vocab] = per_impl
    crossover = next(
        (
            v
            for v in vocabs
            if "onehot" in results[v]
            and results[v]["onehot"] >= results[v]["take"]
        ),
        None,
    )
    return {
        "samples_per_sec_by_vocab": results,
        "unit": "lookups/s",
        "batch": batch,
        "auto_threshold": AUTO_ONEHOT_THRESHOLD,
        "measured_crossover_vocab": crossover,
        "note": (
            "single-chip numbers; sharded tables additionally favor "
            "onehot (contraction partitions over tp, take would gather "
            "cross-chip)"
        ),
    }


def bench_dlrm_criteo_scale():
    """Criteo-SCALE end-to-end: >=1M synthetic rows x 26 tables through
    the ETL engine (cluster dataframe -> MLDataset) into a DLRM fit —
    the full reference pipeline shape (pytorch_dlrm.ipynb) at data
    volume, not a toy table."""
    import optax
    import pandas as pd

    import raydp_tpu
    import raydp_tpu.dataframe as rdf
    from raydp_tpu.data.ml_dataset import MLDataset
    from raydp_tpu.models.dlrm import DLRMConfig, PackedDLRM
    from raydp_tpu.train.estimator import JAXEstimator

    n_rows = 200_000 if _ON_CPU else 1_048_576
    n_tables = 26
    vocabs = tuple(
        [100_000] * 8 + [10_000] * 10 + [1_000] * 8
    ) if not _ON_CPU else tuple([10_000] * 8 + [1_000] * 18)
    cfg = DLRMConfig(
        vocab_sizes=vocabs, embed_dim=64, bottom_mlp=(256, 128, 64),
        top_mlp=(512, 256, 128),
    )
    rs = np.random.RandomState(7)
    dense_cols = [f"d{i}" for i in range(cfg.dense_features)]
    sparse_cols = [f"c{i}" for i in range(n_tables)]
    pdf = pd.DataFrame(
        {
            **{
                c: rs.rand(n_rows).astype(np.float32) for c in dense_cols
            },
            **{
                c: rs.randint(0, vocabs[i], n_rows).astype(np.int32)
                for i, c in enumerate(sparse_cols)
            },
            "click": (rs.rand(n_rows) < 0.25).astype(np.float32),
        }
    )
    session = raydp_tpu.init(app_name="bench-criteo", num_workers=4)
    try:
        t0 = time.perf_counter()
        df = rdf.from_pandas(pdf, num_partitions=8)
        # A light per-column transform so etl_seconds covers a real
        # dataframe stage, not just ingestion (the reference notebook
        # normalizes its dense columns at this point).
        for c in dense_cols[:4]:
            df = df.withColumn(c, rdf.col(c) * 2.0)
        ds = MLDataset.from_df(df, num_shards=2)
        etl_s = time.perf_counter() - t0
        est = JAXEstimator(
            model=PackedDLRM(cfg=cfg),
            optimizer=optax.adagrad(1e-2),
            loss="bce",
            num_epochs=2,
            batch_size=DLRM_BATCH,
            feature_columns=dense_cols + sparse_cols,
            label_column="click",
            shuffle=False,
            epoch_mode="stream",
        )
        ours = _steady(est.fit(ds))
        cluster_agg = _cluster_aggregate(session)
    finally:
        raydp_tpu.stop()
    return {
        "samples_per_sec": round(ours, 1),
        "unit": "samples/s",
        "rows": n_rows,
        "tables": n_tables,
        "etl_seconds": round(etl_s, 2),
        "vs_baseline": None,
        "cluster_telemetry": cluster_agg,
        "baseline": "none (scale config; dlrm_criteo carries the torch baseline)",
    }


def bench_etl_overlap():
    """Streaming pipelined execution vs the stage barrier: the same
    ETL -> MLDataset -> fit pipeline as dlrm_criteo_scale (fewer rows)
    run once with RAYDP_TPU_STREAMING=0 (every stage barriers on full
    partition lists) and once streaming (narrow stages + epoch-0 ingest
    consume partitions as their futures land). Reports both wall-clocks
    plus the measured ETL/ingest overlap seconds and fraction."""
    import optax
    import pandas as pd

    import raydp_tpu
    import raydp_tpu.dataframe as rdf
    from raydp_tpu.data.ml_dataset import MLDataset
    from raydp_tpu.models.dlrm import DLRMConfig, PackedDLRM
    from raydp_tpu.telemetry.overlap import OVERLAP_COUNTER
    from raydp_tpu.train.estimator import JAXEstimator
    from raydp_tpu.utils.profiling import metrics as _metrics

    n_rows = 120_000 if _ON_CPU else 400_000
    n_tables = 8
    vocabs = tuple([10_000] * 2 + [1_000] * 6)
    cfg = DLRMConfig(
        vocab_sizes=vocabs, embed_dim=16, bottom_mlp=(64, 32, 16),
        top_mlp=(64, 32),
    )
    rs = np.random.RandomState(11)
    dense_cols = [f"d{i}" for i in range(cfg.dense_features)]
    sparse_cols = [f"c{i}" for i in range(n_tables)]
    pdf = pd.DataFrame(
        {
            **{c: rs.rand(n_rows).astype(np.float32) for c in dense_cols},
            **{
                c: rs.randint(0, vocabs[i], n_rows).astype(np.int32)
                for i, c in enumerate(sparse_cols)
            },
            "click": (rs.rand(n_rows) < 0.25).astype(np.float32),
        }
    )

    def run(streaming: bool):
        prev = os.environ.get("RAYDP_TPU_STREAMING")
        os.environ["RAYDP_TPU_STREAMING"] = "1" if streaming else "0"
        session = raydp_tpu.init(
            app_name=f"bench-overlap-{int(streaming)}", num_workers=4
        )
        try:
            before = _metrics.snapshot()["counters"].get(OVERLAP_COUNTER, 0.0)
            t0 = time.perf_counter()
            df = rdf.from_pandas(pdf, num_partitions=8)
            for c in dense_cols:
                df = df.withColumn(c, rdf.col(c) * 2.0)
            # num_shards=1: the epoch-0 prefix streamer serves rank 0 from
            # the dataset prefix, so a single shard overlaps end-to-end.
            ds = MLDataset.from_df(df, num_shards=1)
            est = JAXEstimator(
                model=PackedDLRM(cfg=cfg),
                optimizer=optax.adagrad(1e-2),
                loss="bce",
                num_epochs=1,
                batch_size=DLRM_BATCH,
                feature_columns=dense_cols + sparse_cols,
                label_column="click",
                shuffle=False,
                epoch_mode="stream",
            )
            history = est.fit(ds)
            wall = time.perf_counter() - t0
            after = _metrics.snapshot()["counters"].get(OVERLAP_COUNTER, 0.0)
        finally:
            raydp_tpu.stop()
            if prev is None:
                os.environ.pop("RAYDP_TPU_STREAMING", None)
            else:
                os.environ["RAYDP_TPU_STREAMING"] = prev
        return wall, after - before, history[-1]["train_loss"]

    barrier_wall, barrier_overlap, barrier_loss = run(streaming=False)
    stream_wall, stream_overlap, stream_loss = run(streaming=True)
    return {
        "barriered_wall_s": round(barrier_wall, 2),
        "streaming_wall_s": round(stream_wall, 2),
        # Rate leaves (*_per_sec) are what scripts/bench_compare.py
        # diffs between revisions — a streaming-path slowdown gates.
        "streaming_rows_per_sec": round(n_rows / max(1e-9, stream_wall), 1),
        "barriered_rows_per_sec": round(n_rows / max(1e-9, barrier_wall), 1),
        "speedup": round(barrier_wall / max(1e-9, stream_wall), 3),
        "overlap_seconds": round(stream_overlap, 3),
        "overlap_fraction": round(stream_overlap / max(1e-9, stream_wall), 3),
        "barriered_overlap_seconds": round(barrier_overlap, 3),
        "rows": n_rows,
        "tables": n_tables,
        "train_loss_delta": round(abs(stream_loss - barrier_loss), 9),
        "unit": "s",
    }


def bench_attention_kernels():
    """Raw attention-OP microbench: flash vs dense fwd+bwd at a constant
    token budget (batch = TOKENS // seq), H=8 D=64. The kernel-level
    view underneath bench_longcontext's full-model numbers — isolates
    the attention impl from embedding/FFN/optimizer work, so a flash
    regression shows here even when the model bench hides it behind
    GEMM time."""
    import jax
    import jax.numpy as jnp

    from raydp_tpu.ops.attention import reference_attention
    from raydp_tpu.ops.flash_attention import flash_attention

    tokens, heads, head_dim = 16384, 8, 64
    seqs = [512, 1024] if _ON_CPU else [2048, 8192]
    # f32 on CPU for the same reason as the model benches; bf16 is the
    # MXU-native dtype on chip.
    dtype = jnp.float32 if _ON_CPU else jnp.bfloat16
    iters = 4 if _ON_CPU else 20

    def loss_of(attn):
        def f(q, k, v):
            return jnp.sum(attn(q, k, v, causal=True).astype(jnp.float32))

        return jax.jit(jax.grad(f, argnums=(0, 1, 2)))

    results = {}
    for seq in seqs:
        if _over_deadline(margin=60.0):
            results[seq] = {"skipped": "bench deadline"}
            continue
        batch = max(1, tokens // seq)
        rng = np.random.default_rng(0)
        shape = (batch, seq, heads, head_dim)
        q = jnp.asarray(rng.standard_normal(shape), dtype)
        k = jnp.asarray(rng.standard_normal(shape), dtype)
        v = jnp.asarray(rng.standard_normal(shape), dtype)
        per_seq = {"batch": batch}
        for name, fn in (
            ("dense", loss_of(reference_attention)),
            ("flash", loss_of(flash_attention)),
        ):
            try:
                # Bracket with a host fetch (see _timed_train_steps).
                grads = fn(q, k, v)  # compile + warmup
                float(jnp.sum(grads[0].astype(jnp.float32)))
                t0 = time.perf_counter()
                for _ in range(iters):
                    grads = fn(q, k, v)
                float(jnp.sum(grads[0].astype(jnp.float32)))
                dt = (time.perf_counter() - t0) / iters
                per_seq[name] = {
                    "step_ms": round(dt * 1e3, 2),
                    "tokens_per_sec": round(batch * seq / dt, 1),
                }
            except Exception as exc:  # OOM and friends: record, continue
                per_seq[name] = f"{type(exc).__name__}: {str(exc)[:80]}"
        results[seq] = per_seq
    return {
        "fwd_bwd_by_seq": results,
        "unit": "tokens/s",
        "heads": heads,
        "head_dim": head_dim,
        "token_budget": tokens,
    }


def bench_longcontext():
    """Sequence-length scaling on the live device: flash attention vs
    the dense stack at seq 2k-16k (single chip). Records samples/s per
    length per impl and where dense falls over (OOM / collapse) —
    SURVEY §5.7 long-context evidence, extending the seq-2048 CPU run
    of r2 (commit dc63ccb)."""
    import jax
    import jax.numpy as jnp
    import optax

    from raydp_tpu.models.transformer import CausalLM, TransformerConfig

    seqs = [512, 1024] if _ON_CPU else [2048, 4096, 8192, 16384]
    results = {}
    for impl in ("dense", "flash"):
        per_seq = {}
        for seq in seqs:
            if _over_deadline(margin=90.0):
                per_seq[seq] = {"skipped": "bench deadline"}
                continue
            batch = max(1, (8192 if not _ON_CPU else 2048) // seq)
            cfg = TransformerConfig(
                vocab_size=8192,
                n_layers=4,
                n_heads=8,
                d_model=512,
                d_ff=2048,
                max_len=seq,
                causal=True,
                dropout_rate=0.0,
                attention_impl=impl,
                dtype=jnp.bfloat16,
            )
            model = CausalLM(cfg=cfg)
            rs = np.random.RandomState(0)
            ids = jnp.asarray(
                rs.randint(0, cfg.vocab_size, size=(batch, seq))
            )
            def loss_fn(p, ids):
                logits = model.apply(p, ids)
                tgt = jnp.roll(ids, -1, axis=1)
                ll = jax.nn.log_softmax(logits.astype(jnp.float32))
                return -jnp.mean(
                    jnp.take_along_axis(ll, tgt[..., None], axis=-1)
                )

            try:
                params = model.init(jax.random.PRNGKey(0), ids)
                n_steps = 4
                dt = _timed_train_steps(
                    loss_fn, params, optax.adamw(1e-4), (ids,),
                    n_steps=n_steps,
                )
                per_seq[seq] = {
                    "tokens_per_sec": round(n_steps * batch * seq / dt, 1),
                    "batch": batch,
                }
            except Exception as exc:  # OOM and friends: record, continue
                per_seq[seq] = {
                    "error": f"{type(exc).__name__}: {str(exc)[:120]}"
                }
            # Free before the next config.
            params = None
            import gc

            gc.collect()
        results[impl] = per_seq
    return {
        "tokens_per_sec_by_impl": results,
        "unit": "tokens/s",
        "note": (
            "single-chip; ring attention additionally scales seq over "
            "the sp mesh axis (tests/test_attention.py ring-vs-dense "
            "parity; dryrun_multichip exercises the sp sharding)"
        ),
    }


def bench_etl_window():
    """Window-function throughput (the reference's DLRM preprocessing
    idiom: row_number().over(partitionBy(...).orderBy(desc(...))) —
    examples/pytorch_dlrm.ipynb assign_id_with_window), plus a running
    sum, against the equivalent single-process pandas transforms."""
    import pandas as pd

    import raydp_tpu
    import raydp_tpu.dataframe as rdf
    from raydp_tpu.dataframe import window as W

    n_rows = 1_500_000  # host-side config: full size regardless of mode
    rng = np.random.RandomState(11)
    pdf = pd.DataFrame(
        {
            "g": rng.randint(0, 5_000, n_rows),
            "v": rng.randn(n_rows),
            "t": rng.randint(0, 1_000_000, n_rows),
        }
    )
    session = raydp_tpu.init(app_name="bench-window", num_workers=4)
    try:
        df = rdf.from_pandas(pdf, num_partitions=8)
        w = W.Window.partitionBy("g").orderBy(W.desc("t"))
        df.withColumn("r", W.row_number().over(w)).count()  # warm
        dt = float("inf")
        for _ in range(3):  # best-of-3: single-run noise on shared hosts
            t0 = time.perf_counter()
            out = (
                df.withColumn("r", W.row_number().over(w))
                .withColumn("rsum", W.window_sum("v").over(w))
                .to_pandas()
            )
            dt = min(dt, time.perf_counter() - t0)
        assert len(out) == n_rows
        ours = n_rows / dt
        cluster_agg = _cluster_aggregate(session)
    finally:
        raydp_tpu.stop()

    db = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        spdf = pdf.sort_values(["g", "t"], ascending=[True, False])
        grouped = spdf.groupby("g", sort=False)
        spdf.assign(r=grouped.cumcount() + 1, rsum=grouped["v"].cumsum())
        db = min(db, time.perf_counter() - t0)
    base = n_rows / db

    return {
        "rows_per_sec": round(ours, 1),
        "unit": "rows/s",
        "vs_baseline": round(ours / base, 3),
        "host_cpus": os.cpu_count(),
        "cluster_telemetry": cluster_agg,
        "baseline": "single-process pandas sort+groupby cumulative ops",
    }


def bench_dataplane():
    """Data-plane microbenchmarks behind the r06 zero-copy work: scatter
    bandwidth with control-plane envelope bytes alongside (proof the
    tables ride shm, not RPC), stage dispatch latency at one-RPC-per-task
    vs one-RunTaskBatch-per-worker, and packed-loader chunk rate."""
    import jax
    import pandas as pd
    import pyarrow as pa

    import raydp_tpu
    import raydp_tpu.dataframe as rdf
    from raydp_tpu.cluster.cluster import TaskSpec
    from raydp_tpu.data.ml_dataset import MLDataset
    from raydp_tpu.utils.profiling import metrics

    def _payload() -> float:
        return metrics.snapshot()["counters"].get("rpc/payload_bytes", 0.0)

    n_rows, n_parts = 2_000_000, 16
    rng = np.random.RandomState(13)
    pdf = pd.DataFrame(
        {f"f{i}": rng.randn(n_rows).astype(np.float32) for i in range(8)}
    )
    nbytes = int(pa.Table.from_pandas(pdf).nbytes)
    out = {}
    session = raydp_tpu.init(app_name="bench-dataplane", num_workers=4)
    try:
        # --- scatter: driver tables → worker-held refs ----------------
        rdf.from_pandas(pdf, num_partitions=n_parts).count()  # warm
        scatter_gbps, envelope = 0.0, float("inf")
        for _ in range(3):
            p0 = _payload()
            t0 = time.perf_counter()
            df = rdf.from_pandas(pdf, num_partitions=n_parts)
            refs = df.to_object_refs()
            dt = time.perf_counter() - t0
            scatter_gbps = max(scatter_gbps, nbytes / dt / 1e9)
            envelope = min(envelope, _payload() - p0)
        out["scatter_gbps"] = round(scatter_gbps, 3)
        out["scatter_bytes"] = nbytes
        # Control-plane bytes for the whole scatter: O(refs), not
        # O(table) — the before/after this section exists to record.
        out["scatter_envelope_bytes"] = int(envelope)

        # --- dispatch latency: per-task RPCs vs one batch per worker --
        def noop(t):
            return t

        def task(ctx, ref):
            ctx.get_table(ref)
            return None

        ex = df._executor
        ex.map_partitions(refs, noop)  # warm worker pools
        per_task = batched = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            for f in [
                session.cluster.submit_async(task, r, worker_id=None)
                for r in refs
            ]:
                f.result(timeout=120)
            per_task = min(per_task, time.perf_counter() - t0)
            t0 = time.perf_counter()
            for f in session.cluster.submit_batch(
                [TaskSpec(task, (r,)) for r in refs]
            ):
                f.result(timeout=120)
            batched = min(batched, time.perf_counter() - t0)
        out["dispatch_ms_per_task_rpc"] = round(per_task * 1e3, 2)
        out["dispatch_ms_batched_rpc"] = round(batched * 1e3, 2)
        out["dispatch_speedup"] = round(per_task / batched, 2)
    finally:
        raydp_tpu.stop()

    # --- packed single-transfer loader ---------------------------------
    cols = {f"f{i}": rng.rand(500_000).astype(np.float32) for i in range(16)}
    cols["y"] = rng.rand(500_000).astype(np.float32)
    ds = MLDataset([pa.table(cols)], num_shards=1)
    loader = ds.to_jax(
        feature_columns=[f"f{i}" for i in range(16)],
        label_column="y",
        batch_size=65_536,
        shuffle=False,
        device=jax.devices()[0],
    )
    for _ in loader:  # warm
        pass
    c0 = metrics.snapshot()["counters"].get("ingest/device_puts", 0.0)
    t0 = time.perf_counter()
    for _ in loader:
        pass
    dt = time.perf_counter() - t0
    chunks = metrics.snapshot()["counters"].get("ingest/device_puts", 0.0) - c0
    out["loader_chunks_per_sec"] = round(chunks / dt, 2)
    out["loader_device_puts_per_epoch"] = int(chunks)
    out["unit"] = "GB/s scatter; ms dispatch; chunks/s loader"
    return out


def bench_etl_shuffle():
    """Shuffle engine v2 evidence: (a) the one-pass argsort/take
    partitioner vs the legacy one-filter-scan-per-bucket splitter on the
    same table, (b) elided-vs-forced window→groupBy latency (the
    co-partitioning planner's headline win), (c) groupBy/join/orderBy
    rows/s through the locality-scheduled exchange with the
    local-vs-total shuffle-byte split from the metrics registry."""
    import pandas as pd
    import pyarrow as pa

    import raydp_tpu
    import raydp_tpu.dataframe as rdf
    from raydp_tpu.dataframe import dataframe as D
    from raydp_tpu.dataframe import window as W
    from raydp_tpu.dataframe.dataframe import _hash_bucket, _split_by_bucket
    from raydp_tpu.utils.profiling import metrics

    out = {}
    # --- partitioner microbench (single table, no cluster) ------------
    n_rows, n_buckets = 1_500_000, 16
    rng = np.random.RandomState(17)
    t = pa.table(
        {
            "k": rng.randint(0, 100_000, n_rows),
            "v": rng.randn(n_rows),
            "w": rng.randn(n_rows),
        }
    )
    bucket = _hash_bucket(t, ["k"], n_buckets)

    def legacy_split(table, b, n):
        return [table.filter(pa.array(b == i)) for i in range(n)]

    _split_by_bucket(t, bucket, n_buckets)  # warm
    legacy_split(t, bucket, n_buckets)
    one_pass = legacy = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        _split_by_bucket(t, bucket, n_buckets)
        one_pass = min(one_pass, time.perf_counter() - t0)
        t0 = time.perf_counter()
        legacy_split(t, bucket, n_buckets)
        legacy = min(legacy, time.perf_counter() - t0)
    out["partitioner"] = {
        "one_pass_rows_per_sec": round(n_rows / one_pass, 1),
        "legacy_filter_rows_per_sec": round(n_rows / legacy, 1),
        "speedup": round(legacy / one_pass, 2),
        "buckets": n_buckets,
    }

    # --- cluster phase: elision + locality -----------------------------
    pdf = pd.DataFrame(
        {
            "k": rng.randint(0, 10_000, n_rows),
            "v": rng.randn(n_rows),
        }
    )
    rdim = pd.DataFrame(
        {"k": np.arange(10_000), "dim": rng.randn(10_000)}
    )
    saved = (
        D._EXCHANGE_COALESCE_BYTES,
        D._AGG_COALESCE_BYTES,
        D._COMBINE_COALESCE_BYTES,
    )
    saved_aqe = os.environ.get("RAYDP_TPU_AQE")
    session = raydp_tpu.init(app_name="bench-shuffle", num_workers=4)
    try:
        # Defeat the adaptive coalescers so the timings measure real
        # multi-partition exchanges, not a single-table collapse; pin
        # the runtime replanner OFF for the legacy leaves so their
        # numbers stay diffable against pre-AQE baselines (the aqe_*
        # leaves below run the on/off A/B explicitly).
        D._EXCHANGE_COALESCE_BYTES = 0
        D._AGG_COALESCE_BYTES = 0
        D._COMBINE_COALESCE_BYTES = 0
        os.environ["RAYDP_TPU_AQE"] = "0"

        def counters():
            c = metrics.snapshot().get("counters", {})
            return (
                c.get("shuffle/bytes", 0.0),
                c.get("shuffle/local_bytes", 0.0),
                c.get("shuffle/elided", 0.0),
            )

        b0, l0, e0 = counters()
        df = rdf.from_pandas(pdf, num_partitions=8)
        w = W.Window.partitionBy("k").orderBy("v")
        win = df.withColumn("rn", W.row_number().over(w))._flush()
        win.groupBy("k").agg(("v", "sum")).count()  # warm

        def timed(frame):
            dt = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                frame.groupBy("k").agg(("v", "sum"), ("v", "mean")).count()
                dt = min(dt, time.perf_counter() - t0)
            return dt

        elided_s = timed(win)
        # Same partitions, planner metadata stripped → full re-exchange.
        forced_s = timed(D.DataFrame(win._parts, win._executor))
        out["window_groupby"] = {
            "elided_rows_per_sec": round(n_rows / elided_s, 1),
            "forced_rows_per_sec": round(n_rows / forced_s, 1),
            "elision_speedup": round(forced_s / elided_s, 2),
        }

        dt = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            df.groupBy("k").agg(("v", "sum"), ("v", "mean")).count()
            dt = min(dt, time.perf_counter() - t0)
        out["groupby_rows_per_sec"] = round(n_rows / dt, 1)

        dim = rdf.from_pandas(rdim, num_partitions=4)
        df.join(dim, on="k").count()  # warm
        dt = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            df.join(dim, on="k").count()
            dt = min(dt, time.perf_counter() - t0)
        out["join_rows_per_sec"] = round(n_rows / dt, 1)

        df.orderBy("k").count()  # warm
        dt = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            df.orderBy("k").count()
            dt = min(dt, time.perf_counter() - t0)
        out["orderby_rows_per_sec"] = round(n_rows / dt, 1)

        b1, l1, e1 = counters()
        moved, local = b1 - b0, l1 - l0
        out["shuffle_bytes_total"] = int(moved)
        out["shuffle_local_bytes"] = int(local)
        out["shuffle_locality_ratio"] = (
            round(local / moved, 3) if moved else None
        )
        out["shuffles_elided"] = int(e1 - e0)

        # --- zipfian skewed keys: partition-skew evidence --------------
        # A zipf(1.3) key column concentrates a large fraction of rows
        # in a handful of hash buckets; the stage-stats store reports
        # the resulting max/mean partition-skew ratio the AQE salt rule
        # replans on (the aqe_* leaves below run that A/B; this leaf
        # keeps AQE off so it stays diffable against older baselines).
        from raydp_tpu.telemetry.progress import stage_store

        zkeys = np.minimum(rng.zipf(1.3, n_rows), 10_000) - 1
        zdf = rdf.from_pandas(
            pd.DataFrame({"k": zkeys, "v": rng.randn(n_rows)}),
            num_partitions=8,
        )
        zdf.groupBy("k").agg(("v", "sum")).count()  # warm
        dt = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            zdf.groupBy("k").agg(("v", "sum"), ("v", "mean")).count()
            dt = min(dt, time.perf_counter() - t0)
        # Raw-row exchange (window forces one): the head key's mass
        # lands in one bucket, and the stage stats report the resulting
        # partition-skew ratio the AQE salt rule replans on. The tiered
        # groupBy above exchanges per-key PARTIALS, which is exactly
        # why its latency stays flat under key skew.
        last0 = stage_store.last_id()
        zw = W.Window.partitionBy("k").orderBy("v")
        zdf.withColumn("rn", W.row_number().over(zw))._flush()
        zstats = [
            s for s in stage_store.recent(64) if s.stage_id > last0
        ]
        out["skewed_groupby"] = {
            "zipf_a": 1.3,
            "rows_per_sec": round(n_rows / dt, 1),
            "max_partition_skew": round(
                max((s.skew for s in zstats), default=1.0), 3
            ),
            "stages": len(zstats),
        }

        # --- AQE salted-vs-static A/B ----------------------------------
        # Harder skew (zipf 2.0 puts ~half the mass on the head key),
        # layout pre-built ONCE under AQE=0 so both arms consume the
        # identical skewed frame; arms interleave (salted, static,
        # salted, ...) and report medians, same discipline as the
        # stage-stats overhead leaf. The parallelism win scales with
        # cores — on a 1-CPU host the headline is the skew ratio and
        # the work-unit rebalance, not wall clock.
        z2 = np.minimum(rng.zipf(2.0, n_rows), 10_000) - 1
        zskew = rdf.from_pandas(
            pd.DataFrame({"k": z2, "v": rng.randn(n_rows)}),
            num_partitions=8,
        ).withColumn(
            "rn", W.row_number().over(W.Window.partitionBy("k").orderBy("v"))
        )._flush()
        # Strip planner metadata (same partitions): with exchange keys
        # kept, the static arm would take the tier-0 elided path and
        # the A/B would compare different plan shapes, not the slicing.
        zskew = D.DataFrame(zskew._parts, zskew._executor)
        zrows = [zskew._executor.num_rows(p) for p in zskew._parts]
        input_skew = (
            max(zrows) / (sum(zrows) / len(zrows)) if sum(zrows) else 1.0
        )

        def one_aqe_groupby(aqe_on):
            os.environ["RAYDP_TPU_AQE"] = "1" if aqe_on else "0"
            mark = stage_store.last_id()
            t0 = time.perf_counter()
            zskew.groupBy("k").agg(("v", "sum"), ("v", "mean")).count()
            dt = time.perf_counter() - t0
            # Partial-stage task count: salting slices the hot
            # partition into extra work units, so parts > n_partitions
            # is the rebalance fingerprint.
            parts = max(
                (s.parts_out for s in stage_store.recent(64)
                 if s.stage_id > mark and ":partial" in s.op),
                default=len(zskew._parts),
            )
            return dt, parts

        zdim = rdf.from_pandas(rdim, num_partitions=8)
        zprobe = rdf.from_pandas(
            pd.DataFrame({"k": z2, "v": rng.randn(n_rows)}),
            num_partitions=8,
        )._flush()
        saved_bcast = D._BROADCAST_JOIN_BYTES
        D._BROADCAST_JOIN_BYTES = 0  # force the shuffle-join path

        def one_aqe_join(aqe_on):
            os.environ["RAYDP_TPU_AQE"] = "1" if aqe_on else "0"
            mark = stage_store.last_id()
            t0 = time.perf_counter()
            zprobe.join(zdim, on="k").count()
            dt = time.perf_counter() - t0
            # Worst exchange-output skew this run: salting splits the
            # hot probe bucket, so the salted arm's ratio collapses.
            sk = max(
                (s.skew for s in stage_store.recent(64)
                 if s.stage_id > mark and s.op.startswith("exchange")),
                default=1.0,
            )
            return dt, sk

        try:
            one_aqe_groupby(True), one_aqe_join(True)  # warm both paths
            g_on, g_off, j_on, j_off = [], [], [], []
            gp_on = gp_off = len(zskew._parts)
            js_on = js_off = 1.0
            for i in range(6):
                if i % 2 == 0:
                    dt, gp_on = one_aqe_groupby(True)
                    g_on.append(dt)
                    dt, js_on = one_aqe_join(True)
                    j_on.append(dt)
                else:
                    dt, gp_off = one_aqe_groupby(False)
                    g_off.append(dt)
                    dt, js_off = one_aqe_join(False)
                    j_off.append(dt)
        finally:
            D._BROADCAST_JOIN_BYTES = saved_bcast
            os.environ["RAYDP_TPU_AQE"] = "0"
        for xs in (g_on, g_off, j_on, j_off):
            xs.sort()
        g1, g0 = g_on[len(g_on) // 2], g_off[len(g_off) // 2]
        j1, j0 = j_on[len(j_on) // 2], j_off[len(j_off) // 2]
        out["aqe_groupby"] = {
            "zipf_a": 2.0,
            "salted_rows_per_sec": round(n_rows / g1, 1),
            "static_rows_per_sec": round(n_rows / g0, 1),
            "speedup": round(g0 / g1, 2),
            "input_skew": round(input_skew, 3),
            "partial_parts_salted": int(gp_on),
            "partial_parts_static": int(gp_off),
        }
        out["aqe_join"] = {
            "zipf_a": 2.0,
            "salted_rows_per_sec": round(n_rows / j1, 1),
            "static_rows_per_sec": round(n_rows / j0, 1),
            "speedup": round(j0 / j1, 2),
            "max_partition_skew_static": round(js_off, 3),
            "max_partition_skew_salted": round(js_on, 3),
        }

        # --- stage-stats overhead: the <5% guarantee -------------------
        # Interleaved runs + medians: a single best-of-N on a ~50ms op
        # turns scheduler noise into a fake overhead number.
        def one_groupby():
            t0 = time.perf_counter()
            df.groupBy("k").agg(("v", "sum"), ("v", "mean")).count()
            return time.perf_counter() - t0

        ons, offs = [], []
        try:
            for i in range(10):
                if i % 2:
                    ons.append(one_groupby())
                else:
                    os.environ["RAYDP_TPU_STAGE_STATS"] = "0"
                    offs.append(one_groupby())
                    os.environ.pop("RAYDP_TPU_STAGE_STATS", None)
        finally:
            os.environ.pop("RAYDP_TPU_STAGE_STATS", None)
        ons.sort(), offs.sort()
        stats_on, stats_off = ons[len(ons) // 2], offs[len(offs) // 2]
        out["stage_stats_overhead"] = {
            "enabled_s": round(stats_on, 4),
            "disabled_s": round(stats_off, 4),
            "overhead_frac": round(
                (stats_on - stats_off) / stats_off if stats_off else 0.0, 4
            ),
        }
    finally:
        (
            D._EXCHANGE_COALESCE_BYTES,
            D._AGG_COALESCE_BYTES,
            D._COMBINE_COALESCE_BYTES,
        ) = saved
        if saved_aqe is None:
            os.environ.pop("RAYDP_TPU_AQE", None)
        else:
            os.environ["RAYDP_TPU_AQE"] = saved_aqe
        raydp_tpu.stop()
    out["unit"] = "rows/s"
    out["host_cpus"] = os.cpu_count()
    return out


# ----------------------------------------------------------- job accounting

def bench_job_accounting():
    """Job-accounting-plane overhead evidence (doc/telemetry.md "Job
    accounting & event timeline"): the same host-side ETL pipeline run
    under an explicit job scope with the plane ON vs
    ``RAYDP_TPU_JOB_ACCOUNTING=0`` — interleaved runs + medians, same
    discipline as ``stage_stats_overhead``; budget <5%. Also stamps
    the per-job usage rollup the ON arm produced, so ``bench_compare``
    diffs the attribution itself, not just the latency."""
    import pandas as pd

    import raydp_tpu.dataframe as rdf
    from raydp_tpu import telemetry
    from raydp_tpu.dataframe import dataframe as D
    from raydp_tpu.utils.profiling import metrics as _metrics

    n_rows = 200_000
    rs = np.random.RandomState(7)
    pdf = pd.DataFrame({
        "k": rs.randint(0, 512, n_rows),
        "v": rs.rand(n_rows),
    })

    bench_job = telemetry.mint_job("bench-accounting")

    def one_run():
        df = rdf.from_pandas(pdf, num_partitions=4)
        t0 = time.perf_counter()
        with telemetry.job_scope(bench_job):
            df.groupBy("k").agg({"v": "sum"}).to_pandas()
        return time.perf_counter() - t0

    # Force the real exchange path (a coalesced groupBy moves no bytes,
    # so there would be nothing to attribute).
    saved = (D._EXCHANGE_COALESCE_BYTES, D._AGG_COALESCE_BYTES,
             D._COMBINE_COALESCE_BYTES)
    D._EXCHANGE_COALESCE_BYTES = 0
    D._AGG_COALESCE_BYTES = 0
    D._COMBINE_COALESCE_BYTES = 0
    ons, offs = [], []
    try:
        one_run()  # warm both arms' shared caches
        for i in range(10):
            if i % 2:
                ons.append(one_run())
            else:
                os.environ["RAYDP_TPU_JOB_ACCOUNTING"] = "0"
                offs.append(one_run())
                os.environ.pop("RAYDP_TPU_JOB_ACCOUNTING", None)
    finally:
        os.environ.pop("RAYDP_TPU_JOB_ACCOUNTING", None)
        (D._EXCHANGE_COALESCE_BYTES, D._AGG_COALESCE_BYTES,
         D._COMBINE_COALESCE_BYTES) = saved
    ons.sort(), offs.sort()
    on_s, off_s = ons[len(ons) // 2], offs[len(offs) // 2]
    out = {
        "rows_per_sec": round(n_rows / on_s, 1),
        "unit": "rows/s",
        "enabled_s": round(on_s, 4),
        "disabled_s": round(off_s, 4),
        "overhead_frac": round(
            (on_s - off_s) / off_s if off_s else 0.0, 4
        ),
        "baseline": "same pipeline with RAYDP_TPU_JOB_ACCOUNTING=0",
    }
    report = telemetry.usage_report({"driver": _metrics.snapshot()})
    billed = report["jobs"].get(bench_job.job_id, {}).get("usage", {})
    out["job_usage"] = {k: round(v, 4) for k, v in sorted(billed.items())}
    out["jobs_seen"] = len(report["jobs"])
    return out


# ----------------------------------------------------------- observability

def bench_observability():
    """Observability-plane overhead evidence (doc/telemetry.md "SLO
    engine & dashboard"): the same host-side fit with the time-series
    sampler + SLO engine live at an aggressive 20 Hz cadence vs the
    same threads kill-switched (``RAYDP_TPU_TIMESERIES=0`` /
    ``RAYDP_TPU_SLO=0`` — each tick no-ops, isolating the sampling
    work itself) — interleaved runs + medians, same discipline as
    ``stage_stats_overhead``; budget <5%. Also stamps the store
    footprint and the latency of building + rendering the unified
    dashboard document over the populated registry."""
    import pandas as pd

    from raydp_tpu.models.mlp import MLP
    from raydp_tpu.telemetry import dashboard as _dash
    from raydp_tpu.telemetry.slo import SloConfig, SloEngine
    from raydp_tpu.telemetry.timeseries import (
        TimeSeriesConfig,
        TimeSeriesSampler,
    )
    from raydp_tpu.train.estimator import JAXEstimator

    n_rows, n_feat, batch = 16_384, 14, 256
    rs = np.random.RandomState(13)
    x = rs.rand(n_rows, n_feat).astype(np.float32)
    w = rs.rand(n_feat, 1).astype(np.float32)
    cols = [f"f{i}" for i in range(n_feat)]
    df = pd.DataFrame(x, columns=cols)
    df["label"] = (x @ w).astype(np.float32)

    def one_fit():
        est = JAXEstimator(
            model=MLP(hidden=(64, 32), out_dim=1),
            loss="mse",
            num_epochs=1,
            batch_size=batch,
            feature_columns=cols,
            label_column="label",
            epoch_mode="stream",
        )
        t0 = time.perf_counter()
        est.fit_on_df(df)
        return time.perf_counter() - t0

    def timed_fit(kill_switched):
        if kill_switched:
            os.environ["RAYDP_TPU_TIMESERIES"] = "0"
            os.environ["RAYDP_TPU_SLO"] = "0"
        sampler = TimeSeriesSampler(config=TimeSeriesConfig(
            interval_s=0.05, capacity=512, max_series=1024,
        )).start()
        engine = SloEngine(
            store=sampler.store,
            config=SloConfig(interval_s=0.05),
        ).start()
        try:
            dt = one_fit()
        finally:
            engine.stop()
            sampler.stop()
            os.environ.pop("RAYDP_TPU_TIMESERIES", None)
            os.environ.pop("RAYDP_TPU_SLO", None)
        return dt, sampler

    one_fit()  # warm the jit caches both arms share
    ons, offs = [], []
    store_stats = None
    for i in range(10):
        if i % 2 == 0:
            dt, sampler = timed_fit(kill_switched=False)
            ons.append(dt)
            store_stats = sampler.store.stats()
        else:
            offs.append(timed_fit(kill_switched=True)[0])
    ons.sort(), offs.sort()
    on_s, off_s = ons[len(ons) // 2], offs[len(offs) // 2]

    t0 = time.perf_counter()
    dash = _dash.local_dashboard()
    _dash.format_dashboard(dash)
    dash_ms = (time.perf_counter() - t0) * 1e3
    return {
        "samples_per_sec": round(n_rows / on_s, 1),
        "unit": "samples/s",
        "enabled_s": round(on_s, 4),
        "disabled_s": round(off_s, 4),
        "overhead_frac": round(
            (on_s - off_s) / off_s if off_s else 0.0, 4
        ),
        "baseline": "same fit, sampler+engine kill-switched via env",
        "dashboard_build_ms": round(dash_ms, 2),
        "store_series": (store_stats or {}).get("series"),
        "store_memory_bytes_est": (store_stats or {}).get(
            "memory_bytes_est"
        ),
    }


def bench_fault_tolerance():
    """Recovery-cost evidence (doc/fault_tolerance.md): the same tiny
    supervised ``fit_spmd`` run twice — clean, then with an injected
    rank kill on a checkpoint boundary — and the delta reported as
    MTTR (detection + backoff + relaunch + resume; replay is zero by
    construction since the kill lands right after a mid-step save).
    Loss parity between the arms is the correctness gate."""
    import pandas as pd

    import raydp_tpu.dataframe as rdf
    from raydp_tpu.data import MLDataset
    from raydp_tpu.train.spmd_fit import fit_spmd
    from raydp_tpu.utils.profiling import metrics as _metrics

    n_rows, batch = 2_048, 256
    rs = np.random.RandomState(5)
    a, b = rs.randn(n_rows), rs.randn(n_rows)
    pdf = pd.DataFrame({"a": a, "b": b, "y": 2 * a - 3 * b + 1})
    ds = MLDataset.from_df(
        rdf.from_pandas(pdf, num_partitions=2), num_shards=1
    )

    def factory_builder(ckpt):
        def make_estimator():
            import jax
            import optax

            from raydp_tpu.models import MLP
            from raydp_tpu.parallel import MeshSpec
            from raydp_tpu.train import JAXEstimator

            return JAXEstimator(
                model=MLP(hidden=(16,), out_dim=1),
                optimizer=optax.adam(3e-2),
                loss="mse", num_epochs=2, batch_size=batch,
                feature_columns=["a", "b"], label_column="y",
                mesh=MeshSpec(dp=len(jax.devices())), seed=0,
                shuffle=False, epoch_mode="stream",
                checkpoint_dir=ckpt, save_every_steps=2,
            )

        return make_estimator

    root = tempfile.mkdtemp(prefix="bench-ft-")
    t0 = time.perf_counter()
    clean = fit_spmd(
        factory_builder(os.path.join(root, "clean")), ds, world_size=1,
        env={"JAX_PLATFORMS": "cpu"}, timeout=300,
    )
    clean_s = time.perf_counter() - t0

    chaos_ck = os.path.join(root, "chaos")
    t0 = time.perf_counter()
    chaos = fit_spmd(
        factory_builder(chaos_ck), ds, world_size=1,
        env={
            "JAX_PLATFORMS": "cpu",
            # step 4 is a save_every_steps boundary: the mid checkpoint
            # commits, then the rank dies -> replay 0
            "RAYDP_TPU_FAULT_PLAN": "kill:rank=0,step=4",
        },
        timeout=300, checkpoint_dir=chaos_ck,
        restart_backoff_s=0.5,
    )
    chaos_s = time.perf_counter() - t0

    counters = _metrics.snapshot().get("counters", {})
    clean_loss = clean["history"][-1]["train_loss"]
    chaos_loss = chaos["history"][-1]["train_loss"]
    return {
        "samples_per_sec": round(2 * n_rows / chaos_s, 1),
        "unit": "samples/s",
        "clean_s": round(clean_s, 3),
        "chaos_s": round(chaos_s, 3),
        "mttr_s": round(chaos_s - clean_s, 3),
        "restarts": chaos["restarts"],
        "replay_steps": int(counters.get("replay/steps", 0)),
        "clean_loss": round(float(clean_loss), 6),
        "chaos_loss": round(float(chaos_loss), 6),
        "loss_parity": bool(
            abs(chaos_loss - clean_loss) <= 1e-4 * abs(clean_loss)
        ),
        "baseline": "identical fit without RAYDP_TPU_FAULT_PLAN",
    }


def bench_multi_tenant():
    """Control-plane evidence (doc/scheduling.md): (a) fair-share —
    two equal ETL tenants at different priorities contend for one
    arbiter slot through stage turns, reported as throughput plus the
    usage-ledger task-seconds split; (b) preemption MTTR — a
    high-priority arrival evicts a low-priority training gang,
    measured sched/preempt -> sched/resume on the event timeline; (c)
    queue-wait p50 from the arbiter report. Victim/arrival loss
    parity with the ledger split is the correctness signal."""
    import threading

    import pandas as pd

    import raydp_tpu.dataframe as rdf
    from raydp_tpu import control, telemetry
    from raydp_tpu.data import MLDataset
    from raydp_tpu.telemetry import events as _events
    from raydp_tpu.train.spmd_fit import fit_spmd
    from raydp_tpu.utils.profiling import metrics as _metrics

    out = {}
    control.reset_for_tests()
    try:
        arb = control.configure(capacity=1, admit_timeout_s=240.0)

        # -- (a) fair-share ETL split under turn contention ----------
        n_rows, etl_iters = 60_000, 4
        rs = np.random.RandomState(11)
        pdf = pd.DataFrame({
            "k": rs.randint(0, 256, n_rows),
            "v": rs.rand(n_rows),
        })
        hi = telemetry.mint_job("mt-hi", priority=4)
        lo = telemetry.mint_job("mt-lo", priority=0)
        tenant_s = {}

        def tenant(key, job):
            t0 = time.perf_counter()
            with telemetry.job_scope(job):
                for _ in range(etl_iters):
                    rdf.from_pandas(pdf, num_partitions=4) \
                        .groupBy("k").agg({"v": "sum"}).to_pandas()
            tenant_s[key] = time.perf_counter() - t0

        # Force the real exchange path so the usage ledger has bytes
        # to attribute (coalesced groupBys move nothing) — same
        # discipline as bench_job_accounting. task_seconds is billed
        # by cluster ETL workers only, so the driver-local split is
        # read from shuffle_bytes instead.
        from raydp_tpu.dataframe import dataframe as D
        saved = (D._EXCHANGE_COALESCE_BYTES, D._AGG_COALESCE_BYTES,
                 D._COMBINE_COALESCE_BYTES)
        D._EXCHANGE_COALESCE_BYTES = 0
        D._AGG_COALESCE_BYTES = 0
        D._COMBINE_COALESCE_BYTES = 0
        t0 = time.perf_counter()
        try:
            threads = [
                threading.Thread(target=tenant, args=(k, j))
                for k, j in (("hi", hi), ("lo", lo))
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        finally:
            (D._EXCHANGE_COALESCE_BYTES, D._AGG_COALESCE_BYTES,
             D._COMBINE_COALESCE_BYTES) = saved
        etl_s = time.perf_counter() - t0
        usage = telemetry.usage_report({"driver": _metrics.snapshot()})
        hi_sb = usage["jobs"].get(hi.job_id, {}) \
            .get("usage", {}).get("shuffle_bytes", 0.0)
        lo_sb = usage["jobs"].get(lo.job_id, {}) \
            .get("usage", {}).get("shuffle_bytes", 0.0)
        out["etl_rows_per_sec"] = round(2 * etl_iters * n_rows / etl_s, 1)
        out["tenant_wall_s"] = {
            k: round(v, 3) for k, v in sorted(tenant_s.items())
        }
        out["ledger_shuffle_bytes"] = {"hi": hi_sb, "lo": lo_sb}
        # Equal offered work -> the split converging on 0.5 is the
        # fairness evidence; a hi-skewed split means lo was starved.
        out["fair_share_hi_frac"] = round(
            hi_sb / (hi_sb + lo_sb) if hi_sb + lo_sb else 0.0, 4
        )

        # -- (b) scheduler-driven preemption MTTR --------------------
        n_train = 2_048
        a, b = rs.randn(n_train), rs.randn(n_train)
        tpdf = pd.DataFrame({"a": a, "b": b, "y": 2 * a - 3 * b + 1})
        ds = MLDataset.from_df(
            rdf.from_pandas(tpdf, num_partitions=2), num_shards=1
        )
        arrival_ds = MLDataset.from_df(
            rdf.from_pandas(tpdf.head(512), num_partitions=2),
            num_shards=1,
        )

        def factory_builder(ckpt, num_epochs, save_every=0):
            def make_estimator():
                import jax
                import optax

                from raydp_tpu.models import MLP
                from raydp_tpu.parallel import MeshSpec
                from raydp_tpu.train import JAXEstimator

                return JAXEstimator(
                    model=MLP(hidden=(16,), out_dim=1),
                    optimizer=optax.adam(3e-2),
                    loss="mse", num_epochs=num_epochs, batch_size=128,
                    feature_columns=["a", "b"], label_column="y",
                    mesh=MeshSpec(dp=len(jax.devices())), seed=0,
                    shuffle=False, epoch_mode="stream",
                    checkpoint_dir=ckpt, save_every_steps=save_every,
                )

            return make_estimator

        root = tempfile.mkdtemp(prefix="bench-mt-")
        victim_dir = os.path.join(root, "victim")
        victim_job = telemetry.mint_job("mt-victim", priority=0)
        victim_out = {}

        def run_victim():
            with telemetry.job_scope(victim_job):
                try:
                    victim_out["res"] = fit_spmd(
                        factory_builder(victim_dir, 8, save_every=2),
                        ds, world_size=1,
                        env={"JAX_PLATFORMS": "cpu"}, timeout=300,
                        checkpoint_dir=victim_dir,
                    )
                except Exception as exc:  # noqa: BLE001 - reported
                    victim_out["err"] = repr(exc)

        t0 = time.perf_counter()
        vt = threading.Thread(target=run_victim, daemon=True)
        vt.start()
        # Preempt only once the victim is mid-epoch (first periodic
        # checkpoint committed), same discipline as SCHED_SMOKE.
        mid = os.path.join(victim_dir, "step_mid_2", "_METADATA")
        deadline = time.monotonic() + 240.0
        while time.monotonic() < deadline and not os.path.isfile(mid):
            time.sleep(0.05)
        with telemetry.job_scope(telemetry.mint_job("mt-arrival",
                                                    priority=5)):
            arrival = fit_spmd(
                factory_builder(None, 1), arrival_ds, world_size=1,
                env={"JAX_PLATFORMS": "cpu"}, timeout=300,
            )
        vt.join(300.0)
        wall_s = time.perf_counter() - t0

        victim = victim_out.get("res") or {}
        mttr = _events.mttr_report(_events.local_events()) \
            .get(victim_job.job_id, {})
        preempt_eps = [
            e for e in mttr.get("episodes", [])
            if e["start_kind"] == "sched/preempt"
        ]
        out.update({
            # victim + arrival samples over the contended wall time
            "samples_per_sec": round(
                (8 * n_train + 512) / wall_s, 1
            ),
            "unit": "samples/s",
            "preemptions": len(preempt_eps),
            "preempt_mttr_s": round(preempt_eps[0]["repair_s"], 3)
            if preempt_eps else None,
            "victim_restarts": victim.get("restarts"),
            "arrival_restarts": arrival["restarts"],
            "victim_err": victim_out.get("err"),
        })

        # -- (c) queue-wait p50 from the arbiter report --------------
        rep = arb.report()
        out["queue_wait_p50_s"] = rep.get("wait_p50_s")
        # sched/wait/<job_id> keys are per-run-unique: keep only the
        # aggregate families so bench_compare diffs stay stable.
        out["sched_counters"] = {
            k: v for k, v in sorted(
                _metrics.snapshot().get("counters", {}).items()
            ) if k.startswith(("sched/preemptions/", "sched/sheds"))
        }
    finally:
        # The matrix shares this process: later entries must not run
        # under a capacity-1 arbiter.
        control.reset_for_tests()
    return out


def _capture_gang_profile() -> dict:
    """``--profile``: spin a 2-rank SPMD gang running a small stream
    fit and gang-capture a trace mid-training; the merged Perfetto path
    stamps into the result JSON. CPU-pinned
    (the evidence is the machinery, not chip speed)."""
    import threading as _threading

    from raydp_tpu.spmd.job import SPMDJob

    out_dir = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_profile"
    )

    def rank_fit(ctx):
        import numpy as np
        import pandas as pd

        from raydp_tpu.models.mlp import MLP
        from raydp_tpu.train.estimator import JAXEstimator

        rs = np.random.RandomState(ctx.rank)
        n_feat = 8
        x = rs.rand(8_192, n_feat).astype(np.float32)
        df = pd.DataFrame(x, columns=[f"f{i}" for i in range(n_feat)])
        df["label"] = x.sum(axis=1).astype(np.float32)
        est = JAXEstimator(
            model=MLP(hidden=(32,), out_dim=1),
            loss="mse",
            num_epochs=4,
            batch_size=256,
            feature_columns=[f"f{i}" for i in range(n_feat)],
            label_column="label",
            epoch_mode="stream",
        )
        est.fit_on_df(df)

    job = SPMDJob(
        "bench-profile", world_size=2,
        env={"JAX_PLATFORMS": "cpu"}, timeout=120.0,
    )
    job.start()
    try:
        results: dict = {}

        def _run():
            try:
                job.run(rank_fit, timeout=300.0)
            except Exception as exc:
                results["error"] = f"{type(exc).__name__}: {exc}"

        t = _threading.Thread(target=_run, daemon=True)
        t.start()
        time.sleep(3.0)  # let both ranks reach steady-state training
        merged = job.capture_profile(seconds=3.0, out_dir=out_dir)
        t.join(timeout=300.0)
        profile = {
            "merged_trace": merged.get("merged_trace"),
            "ranks": merged.get("ranks"),
        }
        if results.get("error"):
            profile["fit_error"] = results["error"]
        if merged.get("errors"):
            profile["capture_errors"] = merged["errors"]
        return profile
    finally:
        job.stop()


def bench_serving():
    """Serving-plane evidence (doc/serving.md): the same replica group
    driven with continuous batching vs a naive one-request-per-dispatch
    loop (``max_batch=1``). The model charges a fixed ~4 ms per
    ``ExecuteBatch``, so the batched/naive throughput ratio isolates
    what batch assembly buys; p50/p99 and batch fill come from the
    group's own stats surface. Result parity across both arms is the
    correctness gate."""
    from raydp_tpu import control
    from raydp_tpu.serve import ReplicaGroup
    from raydp_tpu.utils.profiling import metrics as _metrics

    n_requests = 192
    control.reset_for_tests()  # serving admits through the arbiter

    def make_model():
        # Nested so cloudpickle ships it by value to the replica procs.
        def model(payloads, bucket):
            time.sleep(0.004)
            return [float(sum(p)) for p in payloads]

        return model

    def drive(max_batch, label):
        _metrics.reset()  # stats() reads the process-global registry
        with ReplicaGroup(
            replicas=2, model_fn=make_model(), label=label,
            max_batch=max_batch, slo_ms=20, max_queue=n_requests + 8,
            restart_backoff_s=0.2,
        ).start() as group:
            # start() returns while the replica interpreters are still
            # booting; wait them out so both arms time steady-state
            # serving, not process startup.
            boot_deadline = time.monotonic() + 30.0
            while group.stats()["replicas_alive"] < 2:
                if time.monotonic() >= boot_deadline:
                    raise RuntimeError(
                        f"serving bench ({label}): replicas never came up"
                    )
                time.sleep(0.02)
            group.predict([0] * 8, timeout_s=30.0)  # warm dispatch path
            t0 = time.perf_counter()
            reqs = [group.submit([i % 7] * 8, timeout_s=180.0)
                    for i in range(n_requests)]
            results = [r.wait(timeout=180.0) for r in reqs]
            wall = time.perf_counter() - t0
            expect = [float((i % 7) * 8) for i in range(n_requests)]
            if results != expect:
                raise RuntimeError(
                    f"serving bench ({label}): replies diverged"
                )
            stats = group.stats()
        return wall, stats

    batched_wall, batched = drive(8, "bench-serve-batched")
    naive_wall, _ = drive(1, "bench-serve-naive")
    return {
        "requests": n_requests,
        "requests_per_sec": round(n_requests / batched_wall, 2),
        "latency_p50_ms": round(batched["latency_p50_s"] * 1e3, 3),
        "latency_p99_ms": round(batched["latency_p99_s"] * 1e3, 3),
        "batch_fill": batched["batch_fill"],
        "naive_requests_per_sec": round(n_requests / naive_wall, 2),
        "speedup_vs_naive": round(naive_wall / batched_wall, 2),
    }


def bench_serve_load():
    """Load-observatory evidence (doc/serving.md#load-observatory): an
    open-loop knee ramp against a live two-replica group, reporting
    the max sustainable RPS under the step SLO, plus one probe step at
    80% of the knee for an honest below-knee p99 and the per-phase
    time split. The group's linger window is kept tiny (slo_ms=5) so
    the knee measures execute capacity, not the batching linger floor,
    and ``max_batch=1`` with a ~12 ms model pins that capacity low
    enough (~2/0.012 ≈ 170 rps) that the cliff lands inside the ramp —
    a saturated knee, not a ramp-ceiling artifact."""
    from raydp_tpu import control
    from raydp_tpu.loadgen import (
        GroupTarget, KneeConfig, find_knee, poisson_schedule,
        run_schedule,
    )
    from raydp_tpu.serve import ReplicaGroup
    from raydp_tpu.utils.profiling import metrics as _metrics

    control.reset_for_tests()
    _metrics.reset()

    def make_model():
        # Nested so cloudpickle ships it by value to the replica procs.
        def model(payloads, bucket):
            time.sleep(0.012)
            return [float(sum(p)) for p in payloads]

        return model

    config = KneeConfig(
        start_rps=8.0, max_rps=512.0, step_factor=2.0,
        step_duration_s=1.5, slo_ms=150.0, shed_threshold=0.05,
        bisect_rounds=2, timeout_s=5.0, seed=0,
    )
    with ReplicaGroup(
        replicas=2, model_fn=make_model(), label="bench-serve-load",
        slo_ms=5, max_batch=1, max_queue=512, restart_backoff_s=0.2,
    ).start() as group:
        boot_deadline = time.monotonic() + 30.0
        while group.stats()["replicas_alive"] < 2:
            if time.monotonic() >= boot_deadline:
                raise RuntimeError(
                    "serve_load bench: replicas never came up"
                )
            time.sleep(0.02)
        group.predict([0] * 8, timeout_s=30.0)  # warm dispatch path
        target = GroupTarget(group)
        result = find_knee(target, config)
        probe_rps = max(1.0, 0.8 * result.knee_rps)
        probe = run_schedule(
            target,
            poisson_schedule(
                probe_rps, config.step_duration_s,
                seed=config.seed + 101,
            ),
            timeout_s=config.timeout_s,
        )
    p99 = probe.latency_quantile(0.99)
    fractions = probe.phase_fractions()
    return {
        "knee_rps": round(result.knee_rps, 2),
        "saturated": result.saturated,
        "p99_at_knee_ms": (
            round(result.p99_at_knee_s * 1e3, 3)
            if result.p99_at_knee_s is not None else None
        ),
        "shed_at_knee": round(result.shed_at_knee, 4),
        "ramp_steps": len(result.curve),
        "p99_at_80pct_knee_ms": (
            round(p99 * 1e3, 3) if p99 is not None else None
        ),
        "probe_shed_rate": round(probe.rate("shed"), 4),
        "phase_fractions": {
            k: round(v, 4) for k, v in fractions.items()
        },
    }


def bench_serve_decode():
    """Decode-plane evidence (doc/serving.md#autoregressive-decode):
    the same paged-KV round loop driving a tiny CausalLM, batched
    (all slots admitted up front, continuous batching keeps them full)
    vs one-request-at-a-time over the *same* engine — the replica's
    step cost is fixed by its slot count, so serving sequentially
    wastes the batch and the tokens/s ratio isolates what iteration-
    level scheduling buys. Token-for-token parity between both arms is
    the correctness gate; TTFT comes from the first streamed token of
    each request, and per-round occupancy / KV page fill ride out as
    ``raydp_decode_*`` telemetry families."""
    from raydp_tpu.serve.decode import DecodeConfig, DecodeLoop
    from raydp_tpu.serve.decode import build_transformer_engine
    from raydp_tpu.telemetry import export as _export
    from raydp_tpu.utils.profiling import metrics as _metrics

    n_requests = 16
    max_new = 32
    num_slots = 8
    prompts = [
        [((3 * i + j) % 251) + 1 for j in range(4 + i % 5)]
        for i in range(n_requests)
    ]
    engine = build_transformer_engine(
        num_slots=num_slots, page_tokens=16, seed=0
    )
    config = DecodeConfig.from_env(round_linger_s=0.0)

    def drive(batch):
        """Run ``prompts`` to completion; ``batch`` submits them all
        up front, else one at a time. Returns wall, streams, ttfts,
        and per-round stats."""
        streams: dict = {}
        first_ts: dict = {}

        def on_token(rid, index, token):
            if index == 0:
                first_ts[rid] = time.perf_counter()
            streams.setdefault(rid, []).append(token)

        loop = DecodeLoop(engine, config, on_token=on_token)
        rounds = []
        t0 = time.perf_counter()
        if batch:
            for i, p in enumerate(prompts):
                loop.submit(f"b{i}", p, max_new=max_new)
            while True:
                stats = loop.run_round()
                rounds.append(stats)
                if stats["live"] == 0 and stats["pending"] == 0:
                    break
        else:
            for i, p in enumerate(prompts):
                loop.submit(f"b{i}", p, max_new=max_new)
                while True:
                    stats = loop.run_round()
                    rounds.append(stats)
                    if stats["live"] == 0 and stats["pending"] == 0:
                        break
        wall = time.perf_counter() - t0
        ttfts = sorted(first_ts[rid] - t0 for rid in first_ts)
        return wall, streams, ttfts, rounds

    # One warm pass compiles prefill (bucket 16) and the decode step at
    # every KV bucket the run will touch, so both arms time steady
    # state, not XLA.
    warm = DecodeLoop(engine, config)
    warm.submit("warm", prompts[0], max_new=max_new)
    warm.run_until_idle()

    _metrics.reset()  # the batched arm's run is the exported evidence
    batched_wall, batched_streams, ttfts, rounds = drive(batch=True)
    seq_wall, seq_streams, _, _ = drive(batch=False)
    for i in range(n_requests):
        if batched_streams[f"b{i}"] != seq_streams[f"b{i}"]:
            raise RuntimeError(
                f"serve_decode bench: request {i} streams diverged "
                "between batched and sequential arms"
            )

    tokens = sum(len(s) for s in batched_streams.values())
    speedup = seq_wall / batched_wall
    if speedup < 3.0:
        raise RuntimeError(
            f"serve_decode bench: batched decode only {speedup:.2f}x "
            "sequential (acceptance floor is 3x)"
        )
    prom = _export.render_prometheus({"driver": _metrics.snapshot()})
    decode_families = sorted({
        line.split("{")[0].split(" ")[0]
        for line in prom.splitlines()
        if line.startswith("raydp_decode_")
    })
    if not decode_families:
        raise RuntimeError(
            "serve_decode bench: no raydp_decode_* telemetry exported"
        )
    occupancies = [
        r["live"] / num_slots for r in rounds if r["live"] > 0
    ]
    ttft_p99 = ttfts[min(len(ttfts) - 1, int(0.99 * len(ttfts)))]
    return {
        "requests": n_requests,
        "tokens": tokens,
        "decode_tokens_per_sec": round(tokens / batched_wall, 2),
        "sequential_tokens_per_sec": round(tokens / seq_wall, 2),
        "speedup_vs_sequential": round(speedup, 2),
        "ttft_p50_s": round(ttfts[len(ttfts) // 2], 5),
        "ttft_p99_s": round(ttft_p99, 5),
        "rounds": len(rounds),
        "batch_occupancy_mean": round(
            sum(occupancies) / max(1, len(occupancies)), 4
        ),
        "decode_families_exported": len(decode_families),
    }


def bench_autoscale():
    """Autoscaler evidence (doc/scheduling.md#autoscaling): against a
    real one-worker cluster, sustained admission pressure must grow
    the pool within one evaluation — ``time_to_grow_s`` is the full
    decision→spawn→registration latency — and idleness must drain it
    back, with ``drain_latency_s`` covering victim pick, the graceful
    worker-gone teardown, and in-flight task requeue (an ETL round is
    kept running across the drain; result parity is the correctness
    gate). ``flap_episodes`` must stay 0 by construction."""
    import threading

    import raydp_tpu
    from raydp_tpu import control, telemetry
    from raydp_tpu.control import (
        Autoscaler,
        AutoscalerConfig,
        ClusterProvisioner,
    )

    control.reset_for_tests()
    session = raydp_tpu.init(app_name="bench-autoscale", num_workers=1,
                             memory_per_worker="256MB")
    cluster = session.cluster
    try:
        sc = Autoscaler(ClusterProvisioner(cluster), AutoscalerConfig(
            min_workers=1, max_workers=2, interval_s=0.5,
            up_cooldown_s=0.2, down_cooldown_s=0.2, idle_evals=1,
        ))
        # Real starvation signal: one slot held, one admission queued.
        arb = control.configure(capacity=1, admit_timeout_s=120.0)
        holder = arb.acquire(telemetry.mint_job("holder"), slots=1,
                             preemptible=False)
        waiter_out = {}

        def waiter():
            waiter_out["lease"] = arb.acquire(
                telemetry.mint_job("starved"), slots=1, timeout=120.0,
                preemptible=False,
            )

        wt = threading.Thread(target=waiter, daemon=True)
        wt.start()
        deadline = time.monotonic() + 10.0
        while (time.monotonic() < deadline
               and arb.report()["queue_depth"] != 1):
            time.sleep(0.02)

        t0 = time.perf_counter()
        grew = sc.step()
        time_to_grow = time.perf_counter() - t0
        if grew.verdict != "grow" or len(sc.provisioner.hosts()) != 2:
            raise RuntimeError(f"autoscale bench: no grow ({grew})")
        holder.release()
        wt.join(30.0)
        waiter_out["lease"].release()

        # Keep ETL in flight across the drain: parity proves the
        # worker-gone requeue path, and the drain pays for it inline.
        def task(ctx, i):
            time.sleep(0.05)
            return i

        items = list(range(32))
        etl_out = {"res": []}

        def etl():
            for base in range(0, len(items), 4):
                etl_out["res"].extend(cluster.map_tasks(
                    task, items[base:base + 4], timeout=120.0,
                ))

        et = threading.Thread(target=etl, daemon=True)
        et.start()
        time.sleep(0.2)
        drain_latency = 0.0
        deadline = time.monotonic() + 60.0
        while (time.monotonic() < deadline
               and len(sc.provisioner.hosts()) > 1):
            t0 = time.perf_counter()
            d = sc.step()
            if d.verdict == "shrink":
                drain_latency = time.perf_counter() - t0
            time.sleep(0.1)
        et.join(120.0)
        if etl_out["res"] != items:
            raise RuntimeError("autoscale bench: tasks lost in drain")
        acted = [d.verdict for d in sc.decisions
                 if d.verdict in ("grow", "shrink")]
        flaps = sum(
            1 for a, b in zip(acted, acted[1:])
            if a == "shrink" and b == "grow"
        )
        return {
            "time_to_grow_s": round(time_to_grow, 3),
            "drain_latency_s": round(drain_latency, 3),
            "decisions_total": len(sc.decisions),
            "grow_decisions": acted.count("grow"),
            "shrink_decisions": acted.count("shrink"),
            "flap_episodes": flaps,
            "tasks_lost": 0,
        }
    finally:
        raydp_tpu.stop()
        control.reset_for_tests()


def bench_scale_sim():
    """Control-plane observatory evidence (doc/simulation.md): replay
    a frozen million-arrival diurnal trace over a thousand simulated
    hosts through the *real* arbiter/autoscaler/serve-queue code on
    the virtual clock. ``events_per_sec`` is the simulator's
    throughput headline; the virtual knee over the LOAD_SMOKE-shaped
    service model (2 hosts, batch 1, 12 ms/call) anchors the
    sim-vs-real cross-check; pathology and invariant counts must stay
    zero on the healthy trace — a nonzero here is a control-plane
    regression, not noise."""
    from raydp_tpu import control
    from raydp_tpu.loadgen.knee import KneeConfig
    from raydp_tpu.loadgen.schedules import diurnal_schedule
    from raydp_tpu.sim import ScenarioConfig, run_trace, sim_knee
    from raydp_tpu.utils.profiling import metrics as _metrics

    control.reset_for_tests()
    _metrics.reset()

    # Frozen trace: same seed every run, so events/sec and pathology
    # counts diff cleanly across revisions.
    events = diurnal_schedule(5000.0, 200.0, seed=1)
    result = run_trace(events, ScenarioConfig(
        hosts=1000, max_batch=8, max_queue=4096, slo_ms=250.0,
        timeout_s=5.0,
    ))
    if result.completed != result.arrivals:
        raise RuntimeError(
            f"scale_sim bench: {result.arrivals - result.completed} of "
            f"{result.arrivals} arrivals did not complete"
        )

    knee = sim_knee(
        ScenarioConfig(hosts=2, max_batch=1, service_ms=12.0,
                       slo_ms=5.0, max_queue=512, timeout_s=5.0),
        KneeConfig(start_rps=8.0, max_rps=512.0, step_factor=2.0,
                   step_duration_s=1.5, slo_ms=150.0,
                   shed_threshold=0.05, bisect_rounds=2, seed=0),
    )

    pathology_counts: dict = {}
    for p in result.pathologies:
        pathology_counts[p["kind"]] = (
            pathology_counts.get(p["kind"], 0) + p["count"]
        )
    return {
        "arrivals": result.arrivals,
        "hosts": 1000,
        "completed": result.completed,
        "shed": result.shed,
        "virtual_s": round(result.duration_s, 1),
        "wall_s": round(result.wall_s, 2),
        "events_processed": result.events_processed,
        "events_per_sec": round(result.events_per_s, 1),
        "p50_ms": result.p50_ms,
        "p99_ms": result.p99_ms,
        "invariant_violations": len(result.invariant_violations),
        "pathology_counts": pathology_counts,
        "knee_rps": knee["knee_rps"],
        "knee_saturated": knee["saturated"],
        "knee_steps": knee["steps"],
    }


# ----------------------------------------------------------- main

# One matrix, one process, the default backend. Ordered so the evidence
# the round needs most lands first; every completed entry is streamed to
# the partial sidecar.
MATRIX = [
    ("nyctaxi_mlp", bench_nyctaxi),
    ("etl_groupby_shuffle", bench_etl_groupby),
    ("etl_window", bench_etl_window),
    # Host-side like the ETL configs above: partitioner + planner
    # evidence for the shuffle engine, full size in every mode.
    ("etl_shuffle", bench_etl_shuffle),
    # Host-side like the ETL configs: cluster + loader mechanics, no
    # device math — full size even at the reduced CPU sizes.
    ("dataplane", bench_dataplane),
    # Phase-accounting overhead + fraction evidence (host-side fit).
    # Job-accounting-plane overhead + per-job attribution evidence
    # (host-side ETL under an explicit job scope).
    ("job_accounting", bench_job_accounting),
    # Time-series sampler + SLO engine overhead vs kill-switched
    # baseline, plus dashboard build latency (host-side fit).
    ("observability", bench_observability),
    # Recovery cost (MTTR) of the supervised gang under an injected
    # rank kill; host-side, loss parity is the correctness gate.
    ("fault_tolerance", bench_fault_tolerance),
    # Multi-tenant control plane: fair-share turn split, scheduler
    # preemption MTTR, queue-wait p50 (doc/scheduling.md).
    ("multi_tenant", bench_multi_tenant),
    # Serving plane: continuous batching vs naive per-request dispatch
    # over real replica processes (doc/serving.md).
    ("serving", bench_serving),
    # Load observatory: open-loop knee ramp over the same replica
    # group — max sustainable RPS + phase split (doc/serving.md).
    ("serve_load", bench_serve_load),
    # Decode plane: paged-KV continuous batching vs one-request-at-a-
    # time over the same tiny CausalLM — tokens/s, TTFT, occupancy
    # (doc/serving.md#autoregressive-decode). In-process, CPU-sized.
    ("serve_decode", bench_serve_decode),
    # Self-sizing pool: time-to-scale-up, graceful-drain latency, and
    # flap count against a real worker pool (doc/scheduling.md).
    ("autoscale", bench_autoscale),
    # Virtual-clock observatory: million-arrival replay through the
    # real control plane — events/sec throughput, sim knee, pathology
    # counts (doc/simulation.md). Host-side, deterministic.
    ("scale_sim", bench_scale_sim),
    # Ingest is bandwidth-sensitive: keep it ahead of the model configs
    # that leave host-memory pressure behind.
    ("ingest_device_feed", bench_ingest),
    ("bert_glue", bench_bert),
    ("dlrm_criteo", bench_dlrm),
    ("titanic_classifier", bench_titanic),
    ("dlrm_embedding_study", bench_dlrm_embedding_study),
    ("dlrm_criteo_scale", bench_dlrm_criteo_scale),
    # Host-side A/B of the streaming stage scheduler (barrier vs
    # pipelined) — cluster + loader mechanics, full size in every mode.
    ("etl_overlap", bench_etl_overlap),
    ("longcontext_seq_scaling", bench_longcontext),
    ("attention_kernels", bench_attention_kernels),
]

_STATE = {
    "configs": {},    # name -> stamped result
    "device": None,   # {"platform", "device_kind", "device_count"}
    "profile": None,  # --profile: merged gang trace path
    "analysis": None,  # raydpcheck wall-time (checker perf regression)
    "notes": [],
    "emitted": False,
}


def _partial_path() -> str:
    return os.environ.get(
        "RAYDP_TPU_BENCH_PARTIAL",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "BENCH_partial.json"),
    )


def _write_json_atomic(path: str, obj) -> None:
    tmp = f"{path}.tmp.{os.getpid()}"
    try:
        with open(tmp, "w") as f:
            json.dump(obj, f, default=str)
        os.replace(tmp, path)
    except Exception:
        pass  # a failed sidecar write must never kill the bench


def _assemble() -> dict:
    """Build the final JSON object from whatever has completed."""
    configs = dict(_STATE["configs"])
    taxi = configs.get("nyctaxi_mlp", {})
    out = {
        "metric": "nyctaxi_mlp_train_samples_per_sec",
        "value": taxi.get("samples_per_sec"),
        "unit": "samples/s",
        "vs_baseline": taxi.get("vs_baseline"),
        **(_STATE["device"] or {}),
        "configs": configs,
    }
    if _STATE["profile"]:
        out["profile"] = _STATE["profile"]
    if _STATE["analysis"]:
        out["analysis"] = _STATE["analysis"]
    if _STATE["notes"]:
        out["note"] = "; ".join(_STATE["notes"])
    return out


def _bench_static_analysis() -> None:
    """Time a full raydpcheck pass over raydp_tpu/ so bench_compare
    flags checker slowdowns like any other regression (files_per_sec is
    a rate key it already diffs)."""
    try:
        from raydp_tpu.analysis import run_analysis

        repo_root = os.path.dirname(os.path.abspath(__file__))
        result = run_analysis([os.path.join(repo_root, "raydp_tpu")])
        _STATE["analysis"] = {
            "raydpcheck": {
                "seconds": round(result.seconds, 3),
                "files": result.files,
                "findings": len(result.findings),
                "files_per_sec": round(result.files / result.seconds, 1)
                if result.seconds else None,
            }
        }
    except Exception as exc:  # the checker must never sink the bench
        _STATE["notes"].append(
            f"raydpcheck bench failed: {type(exc).__name__}: {exc}"
        )


def _emit(partial: bool = False) -> None:
    """Print the ONE JSON line. Idempotent; safe from signal context."""
    if _STATE["emitted"]:
        return
    _STATE["emitted"] = True
    out = _assemble()
    if partial:
        out["partial"] = True
    _write_json_atomic(_partial_path(), out)
    print(json.dumps(out, default=str), flush=True)


def _on_signal(signum, frame):
    _STATE["notes"].append(
        f"terminated by signal {signum}; results are partial"
    )
    _emit(partial=True)
    os._exit(1)


def _run_and_stamp(fn) -> dict:
    """Run one bench fn: errors become a result (main() turns any into
    a non-zero exit), the device and wall time are stamped, and the
    process metrics registry (reset per config) is attached — the
    ingest meters / step-timer percentiles behind each number ride
    along in the emitted JSON."""
    from raydp_tpu.utils.memory import host_rss_bytes, reset_peak_rss
    from raydp_tpu.utils.profiling import metrics

    metrics.reset()  # per-config telemetry, not cumulative across configs
    # Fresh peak-RSS window per section; where clear_refs is unsupported
    # the peak is the process lifetime high-water mark instead.
    peak_windowed = reset_peak_rss()
    t0 = time.perf_counter()
    try:
        res = fn()
    except Exception as exc:  # record, keep benching
        res = {"error": f"{type(exc).__name__}: {exc}"}
    res["seconds"] = round(time.perf_counter() - t0, 1)
    res.update(_STATE["device"] or {})
    peak = host_rss_bytes()[1]
    res["peak_rss_bytes"] = peak
    res["peak_rss_windowed"] = peak_windowed
    snap = metrics.snapshot()
    if snap.get("counters") or len(snap) > 1:
        res["telemetry"] = snap
    import gc

    gc.collect()
    return res


def _record(name: str, fn) -> None:
    _STATE["configs"][name] = _run_and_stamp(fn)
    _write_json_atomic(_partial_path(), _assemble())


def _parse_trace_out(argv):
    """``--trace-out [PATH]`` → merged Chrome trace destination (default
    next to the results JSON). Consumes the flag from argv; ensures a
    telemetry dir exists so spans have somewhere to shard — deliberately
    via os.environ, so cluster/SPMD child processes inherit it and their
    shards land in the same merge."""
    if "--trace-out" not in argv:
        return None
    idx = argv.index("--trace-out")
    path = None
    if idx + 1 < len(argv) and not argv[idx + 1].startswith("--"):
        path = argv[idx + 1]
        del argv[idx:idx + 2]
    else:
        del argv[idx]
    if path is None:
        path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "BENCH_trace.json"
        )
    from raydp_tpu.telemetry import TELEMETRY_DIR_ENV

    if not os.environ.get(TELEMETRY_DIR_ENV):
        os.environ[TELEMETRY_DIR_ENV] = tempfile.mkdtemp(
            prefix="raydp-bench-trace-"
        )
    return path


def _write_trace_out(path) -> None:
    try:
        from raydp_tpu.telemetry import (
            flush_spans,
            telemetry_dir,
            write_chrome_trace,
        )

        flush_spans()
        out = write_chrome_trace(telemetry_dir(), path)
        _STATE["notes"].append(f"chrome trace written to {out}")
    except Exception as exc:  # tracing must never sink the bench run
        _STATE["notes"].append(
            f"trace-out failed: {type(exc).__name__}: {exc}"
        )


def main(argv=None):
    argv = list(sys.argv[1:] if argv is None else argv)
    trace_out = _parse_trace_out(argv)
    want_profile = "--profile" in argv
    if want_profile:
        argv.remove("--profile")

    from raydp_tpu.utils.compile_cache import (
        cpu_requested,
        ensure_compile_cache,
    )

    ensure_compile_cache()
    import jax

    dev = jax.devices()[0]
    if dev.platform == "cpu" and not cpu_requested():
        # Never CPU numbers under a device's name: a run that was not
        # asked onto the CPU and finds no chip has nothing to report.
        print(
            "bench: no TPU found (jax.devices() reports platform 'cpu') "
            "and JAX_PLATFORMS=cpu was not asked for; not benchmarking",
            file=sys.stderr,
        )
        return 2
    global _DEADLINE, _ON_CPU
    _ON_CPU = dev.platform == "cpu"
    _STATE["device"] = {
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
    }

    signal.signal(signal.SIGTERM, _on_signal)
    signal.signal(signal.SIGINT, _on_signal)
    # A crash that escapes main() still emits — flagged partial so a
    # died-midway run is distinguishable from a completed one (_emit is
    # idempotent: after main's own final call this is a no-op).
    atexit.register(lambda: _emit(partial=True))

    bench_budget = float(os.environ.get("RAYDP_TPU_BENCH_BUDGET_S", 2700))
    bench_deadline = time.monotonic() + bench_budget
    _DEADLINE = bench_deadline

    wanted = set(_only_filter([n for n, _ in MATRIX]))
    for name, fn in MATRIX:
        if name not in wanted:
            continue
        if bench_deadline - time.monotonic() < 60:
            _STATE["notes"].append(
                f"bench budget exhausted before {name}; matrix truncated"
            )
            break
        _record(name, fn)
    if want_profile:
        try:
            _STATE["profile"] = _capture_gang_profile()
        except Exception as exc:  # profile must never sink the bench
            _STATE["notes"].append(
                f"gang profile failed: {type(exc).__name__}: {exc}"
            )
    if trace_out is not None:
        _write_trace_out(trace_out)
    _bench_static_analysis()
    _emit()
    failed = [
        name for name, res in _STATE["configs"].items() if "error" in res
    ]
    if failed:
        print(f"bench: sections raised: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
