#!/usr/bin/env bash
# Tier-1 verification gate (see ROADMAP.md): the fast, CPU-only test
# suite every change must keep green. Runs from any cwd.
#
#   scripts/verify.sh [extra pytest args]
#
# Prints DOTS_PASSED=<n> (count of progress dots = passing tests) and
# exits with pytest's status.
set -o pipefail
cd "$(dirname "$0")/.."

LOG="${T1_LOG:-/tmp/_t1.log}"
rm -f "$LOG"
# Crash black box for CI: every test-spawned process dumps a postmortem
# bundle here on crash/SIGTERM/watchdog stall; shipped on failure below.
export RAYDP_TPU_POSTMORTEM_DIR="${RAYDP_TPU_POSTMORTEM_DIR:-/tmp/raydp_tpu_postmortem.$$}"
# Query-profiling artifacts: every DataFrame stage the tests execute
# appends its StageStats record here (stats-<pid>.jsonl shards),
# dumped below on failure so CI shows what the engine was doing.
export RAYDP_TPU_STATS_DIR="${RAYDP_TPU_STATS_DIR:-/tmp/raydp_tpu_stats.$$}"
# Machine-readable smoke-gate metrics (preempt MTTR, serve fill,
# time-to-grow, SLO breach-detect/MTTR): each gate below stamps its
# numbers here via scripts/verify_metrics.py, and the sim gate reads the
# load gate's knee from it. Every run starts the file anew.
export VERIFY_METRICS_PATH="${VERIFY_METRICS_PATH:-$PWD/VERIFY_METRICS.json}"
rm -f "$VERIFY_METRICS_PATH"
# On any gate failure, ship the unified dashboard with the black box:
# the same document /debug/dashboard serves, rebuilt offline from the
# gate's telemetry dir (or the local registry when the gate kept none).
dump_dashboard() {
  echo "--- dashboard dump (postmortem) ---"
  JAX_PLATFORMS=cpu python -m raydp_tpu.telemetry.dashboard --json "$@" \
    || echo "(dashboard unavailable)"
}
timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q \
  -m 'not slow' --continue-on-collection-errors \
  -p no:cacheprovider -p no:xdist -p no:randomly "$@" 2>&1 | tee "$LOG"
rc=${PIPESTATUS[0]}
echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' "$LOG" | tr -cd . | wc -c)
if [ "$rc" -ne 0 ]; then
  # Ship the black box with the failure: newest bundle's reason + last
  # flight events (no-op message when nothing crashed).
  echo "--- newest postmortem bundle (if any) ---"
  python -m raydp_tpu.telemetry.flight_recorder "$RAYDP_TPU_POSTMORTEM_DIR" || true
  # Stage-stats tail + live progress: which stages ran last, and what
  # (if anything) was still in flight when the suite died.
  echo "--- last dataframe stage stats (if any) ---"
  newest_shard=$(ls -t "$RAYDP_TPU_STATS_DIR"/stats-*.jsonl 2>/dev/null | head -1)
  if [ -n "$newest_shard" ]; then
    tail -5 "$newest_shard"
  else
    echo "(no stage-stat shards)"
  fi
  echo "--- progress report ---"
  JAX_PLATFORMS=cpu python -c 'import json; from raydp_tpu.telemetry.progress import progress; print(json.dumps(progress.report()))' || true
fi
# Static analysis gate (HARD): raydpcheck must report zero
# non-baselined findings over raydp_tpu/ (rules R1-R5, doc/analysis.md).
# Budget <30s — it runs in ~2s; the JSON report ships on failure like
# the other black boxes above.
if [ "$rc" -eq 0 ]; then
  echo "--- static analysis (raydpcheck) ---"
  check_json="/tmp/raydpcheck.$$.json"
  if timeout -k 5 30 python -m raydp_tpu.analysis raydp_tpu/ \
      --json-out "$check_json"; then
    echo "RAYDPCHECK=ok"
  else
    echo "RAYDPCHECK=failed"
    echo "--- raydpcheck JSON report ---"
    cat "$check_json" 2>/dev/null || echo "(no report written)"
    dump_dashboard
    rc=1
  fi
  rm -f "$check_json"
fi
# EXPLAIN ANALYZE smoke: a window->groupBy pipeline must profile end to
# end and the analyze CLI must fold its stats shards into the report.
if [ "$rc" -eq 0 ]; then
  echo "--- explain-analyze smoke ---"
  smoke_dir=$(mktemp -d)
  JAX_PLATFORMS=cpu RAYDP_TPU_STATS_DIR="$smoke_dir" python - <<'PYEOF' \
    && JAX_PLATFORMS=cpu python -m raydp_tpu.telemetry.analyze "$smoke_dir" >/dev/null \
    && echo "ANALYZE_SMOKE=ok" \
    || { echo "ANALYZE_SMOKE=failed"; dump_dashboard; rc=1; }
import numpy as np, pandas as pd
import raydp_tpu.dataframe as rdf
from raydp_tpu.dataframe import dataframe as D
D._EXCHANGE_COALESCE_BYTES = 0
df = rdf.from_pandas(
    pd.DataFrame({"k": np.arange(4000) % 13, "v": np.arange(4000.0)}),
    num_partitions=4,
)
out = df.withColumn(
    "rn", rdf.row_number().over(rdf.Window.partitionBy("k").orderBy("v"))
).groupBy("k").agg({"v": "max"})
text = out.explain(analyze=True, quiet=True)
assert "== Physical Plan ==" in text and "skew" in text, text
PYEOF
  rm -rf "$smoke_dir"
fi
# AQE smoke (HARD): a parquet-scan -> zipfian groupBy pipeline on a
# 2-worker cluster must replan at runtime — the scan rule pushes the
# projection + predicate into the executor-side parquet read (pruning
# whole files from footer stats) and the coalesce rule merges the
# small post-shuffle buckets the skewed keys leave behind — with every
# decision visible in explain(analyze=True), and the adaptive plan
# must beat the static planner (RAYDP_TPU_AQE=0) on wall clock,
# best-of-3 interleaved. The speedup is stamped into VERIFY_METRICS so
# the drift check below catches regressions in the replan rules
# themselves. doc/performance.md "Adaptive query engine" is the story
# this gate proves end to end.
if [ "$rc" -eq 0 ]; then
  echo "--- aqe smoke (runtime replanning A/B) ---"
  aqe_dir=$(mktemp -d)
  JAX_PLATFORMS=cpu AQE_SMOKE_DIR="$aqe_dir" python - <<'PYEOF' \
    && echo "AQE_SMOKE=ok" \
    || { echo "AQE_SMOKE=failed"; dump_dashboard; rc=1; }
import os
import time

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

# Keep the replan floor below this smoke's data volume; everything
# else runs at the documented defaults.
os.environ["RAYDP_TPU_AQE_MIN_EXCHANGE_MB"] = "0.05"

import raydp_tpu
import raydp_tpu.dataframe as rdf
from raydp_tpu.dataframe import aqe as _aqe
from raydp_tpu.dataframe import col
from raydp_tpu.dataframe import dataframe as D

# Force real exchanges: the coalesced-gather shortcut would swallow
# the exchange before the replan hook ever measured a bucket.
D._EXCHANGE_COALESCE_BYTES = 0
D._AGG_COALESCE_BYTES = 0
D._COMBINE_COALESCE_BYTES = 0

raydp_tpu.init(app_name="aqe-smoke", num_workers=2,
               memory_per_worker="512MB")

data_dir = os.environ["AQE_SMOKE_DIR"]
rng = np.random.RandomState(7)
rows_per_file, n_files = 25_000, 16
for i in range(n_files):
    n = rows_per_file
    t = pa.table({
        "k": np.minimum(rng.zipf(1.3, n), 100_000).astype(np.int64),
        "v": rng.rand(n),
        "ts": np.arange(i * n, (i + 1) * n, dtype=np.int64),
        **{f"b{j}": rng.rand(n) for j in range(5)},
    })
    pq.write_table(t, f"{data_dir}/part-{i:02d}.parquet")


def run(aqe):
    os.environ["RAYDP_TPU_AQE"] = aqe
    t0 = time.monotonic()
    out = (rdf.read_parquet(data_dir)
           .filter(col("ts") < 200_000)
           .select("k", "v")
           .groupBy("k").agg({"v": "sum"}))
    nrows = out.count()
    return time.monotonic() - t0, nrows, out


run("1")  # warm both arms before timing
run("0")
times = {"0": [], "1": []}
rows = set()
for _ in range(3):
    for arm in ("1", "0"):
        dt, nrows, out = run(arm)
        times[arm].append(dt)
        rows.add(nrows)
assert len(rows) == 1, f"adaptive plan changed the result: {rows}"

_, nrows, out = run("1")
text = out.explain(analyze=True, quiet=True)
marks = _aqe.rule_counts(text)
assert marks.get("scan"), f"no scan replan in plan:\n{text}"
assert marks.get("coalesce"), f"no coalesce replan in plan:\n{text}"

best_static, best_aqe = min(times["0"]), min(times["1"])
speedup = best_static / best_aqe
assert speedup > 1.05, (
    f"adaptive plan did not beat static: {best_aqe:.3f}s vs "
    f"{best_static:.3f}s (speedup {speedup:.3f})"
)
print(f"AQE speedup {speedup:.2f}x "
      f"({best_aqe:.3f}s adaptive vs {best_static:.3f}s static), "
      f"replans {marks}")

exec(open("scripts/verify_metrics.py").read())
stamp("aqe_smoke", {
    "aqe_speedup": round(speedup, 3),
    "aqe_rows_per_sec": rows_per_file * n_files / best_aqe,
})
raydp_tpu.stop()
PYEOF
  rm -rf "$aqe_dir"
fi
# Chaos smoke (HARD): a tiny supervised fit with an injected rank kill
# must auto-recover (exactly one restart, resume from the mid-step
# checkpoint) and land on the SAME loss as an uninterrupted run —
# the end-to-end proof that doc/fault_tolerance.md's recovery story
# holds, not just its unit tests.
if [ "$rc" -eq 0 ]; then
  echo "--- chaos smoke (injected rank kill) ---"
  JAX_PLATFORMS=cpu python - <<'PYEOF' \
    && echo "CHAOS_SMOKE=ok" \
    || { echo "CHAOS_SMOKE=failed"; dump_dashboard; rc=1; }
import os
import tempfile

import numpy as np
import pandas as pd

import raydp_tpu.dataframe as rdf
from raydp_tpu.data import MLDataset
from raydp_tpu.train.spmd_fit import fit_spmd


def factory_builder(ckpt):
    def make_estimator():
        import jax
        import optax

        from raydp_tpu.models import MLP
        from raydp_tpu.parallel import MeshSpec
        from raydp_tpu.train import JAXEstimator

        return JAXEstimator(
            model=MLP(hidden=(8,), out_dim=1), optimizer=optax.adam(3e-2),
            loss="mse", num_epochs=2, batch_size=128,
            feature_columns=["a", "b"], label_column="y",
            mesh=MeshSpec(dp=len(jax.devices())), seed=0, shuffle=False,
            epoch_mode="stream", checkpoint_dir=ckpt, save_every_steps=2,
        )

    return make_estimator


rng = np.random.default_rng(0)
a, b = rng.standard_normal(512), rng.standard_normal(512)
pdf = pd.DataFrame({"a": a, "b": b, "y": 2 * a - 3 * b + 1})
ds = MLDataset.from_df(rdf.from_pandas(pdf, num_partitions=2), num_shards=1)
root = tempfile.mkdtemp()
clean = fit_spmd(
    factory_builder(os.path.join(root, "clean")), ds, world_size=1,
    env={"JAX_PLATFORMS": "cpu"}, timeout=300,
)
chaos_ck = os.path.join(root, "chaos")
chaos = fit_spmd(
    factory_builder(chaos_ck), ds, world_size=1,
    env={
        "JAX_PLATFORMS": "cpu",
        "RAYDP_TPU_FAULT_PLAN": "kill:rank=0,step=2",
    },
    timeout=300, checkpoint_dir=chaos_ck,
)
assert chaos["restarts"] == 1, f"expected 1 restart, got {chaos['restarts']}"
assert os.path.isdir(os.path.join(chaos_ck, "step_mid_2")), "no mid ckpt"
np.testing.assert_allclose(
    chaos["history"][-1]["train_loss"],
    clean["history"][-1]["train_loss"], rtol=1e-4,
)
PYEOF
fi
# Job accounting smoke (HARD): two jobs running concurrently on one
# driver must produce disjoint per-job usage (chip-seconds from their
# fits, shuffle bytes from their exchanges) whose sums equal the
# cluster-global totals, and the event-timeline CLI must render a
# non-empty per-job timeline from the same run's shards — the
# end-to-end proof of doc/telemetry.md's "Job accounting & event
# timeline" story.
if [ "$rc" -eq 0 ]; then
  echo "--- job accounting smoke (2 concurrent jobs) ---"
  acct_dir=$(mktemp -d)
  JAX_PLATFORMS=cpu RAYDP_TPU_TELEMETRY_DIR="$acct_dir" python - <<'PYEOF' \
    && JAX_PLATFORMS=cpu python -m raydp_tpu.telemetry.events "$acct_dir" \
         | grep -q "== job" \
    && echo "ACCOUNTING_SMOKE=ok" \
    || { echo "ACCOUNTING_SMOKE=failed"; dump_dashboard "$acct_dir"; rc=1; }
import threading
import time

import numpy as np
import pandas as pd

import raydp_tpu.dataframe as rdf
from raydp_tpu import telemetry
from raydp_tpu.dataframe import dataframe as D
from raydp_tpu.utils.profiling import metrics

_t0 = time.monotonic()

# Force real exchanges: coalesced groupBys move no bytes to attribute.
D._EXCHANGE_COALESCE_BYTES = 0
D._AGG_COALESCE_BYTES = 0
D._COMBINE_COALESCE_BYTES = 0


def workload(job, seed):
    rs = np.random.RandomState(seed)
    pdf = pd.DataFrame(
        {"k": rs.randint(0, 64, 20_000), "v": rs.rand(20_000)}
    )
    with telemetry.job_scope(job):
        rdf.from_pandas(pdf, num_partitions=4) \
            .groupBy("k").agg({"v": "sum"}).to_pandas()
        from raydp_tpu.models.mlp import MLP
        from raydp_tpu.train.estimator import JAXEstimator

        x = rs.rand(256, 2).astype(np.float32)
        tdf = pd.DataFrame(x, columns=["f0", "f1"])
        tdf["label"] = x.sum(axis=1)
        JAXEstimator(
            model=MLP(hidden=(4,), out_dim=1), loss="mse",
            num_epochs=1, batch_size=64,
            feature_columns=["f0", "f1"], label_column="label",
        ).fit_on_df(tdf)


jobs = [telemetry.mint_job("smoke-a"), telemetry.mint_job("smoke-b")]
threads = [
    threading.Thread(target=workload, args=(j, i))
    for i, j in enumerate(jobs)
]
for t in threads:
    t.start()
for t in threads:
    t.join()

report = telemetry.usage_report({"driver": metrics.snapshot()})
assert jobs[0].job_id != jobs[1].job_id
for j in jobs:
    usage = report["jobs"][j.job_id]["usage"]
    assert usage.get("shuffle_bytes", 0) > 0, (j.job_id, usage)
    assert usage.get("chip_seconds", 0) > 0, (j.job_id, usage)
for kind in ("shuffle_bytes", "chip_seconds"):
    total = report["totals"][kind]
    per_job = sum(
        r["usage"].get(kind, 0.0) for r in report["jobs"].values()
    )
    assert abs(total - per_job) <= 1e-6 * max(1.0, total), \
        (kind, total, per_job)

elapsed = time.monotonic() - _t0
exec(open("scripts/verify_metrics.py").read())
stamp("accounting_smoke", {
    "shuffle_bytes_per_sec": report["totals"]["shuffle_bytes"] / elapsed,
    "chip_seconds": report["totals"]["chip_seconds"],
})
PYEOF
  rm -rf "$acct_dir"
fi
# Scheduler smoke (HARD): with the arbiter enabled (capacity 1), a
# high-priority arrival must preempt the running low-priority gang,
# which drains to a step_emergency_* checkpoint and releases its
# slot; the arrival completes untouched, the victim auto-resumes and
# lands on the SAME loss as an unpreempted run (exact-position resume
# — replay bounded by one save_every_steps interval), and the
# event-timeline CLI renders the preempt->resume MTTR episode — the
# end-to-end proof of doc/scheduling.md's preemption story.
if [ "$rc" -eq 0 ]; then
  echo "--- scheduler smoke (priority preemption) ---"
  sched_dir=$(mktemp -d)
  JAX_PLATFORMS=cpu RAYDP_TPU_TELEMETRY_DIR="$sched_dir" python - <<'PYEOF' \
    && JAX_PLATFORMS=cpu python -m raydp_tpu.telemetry.events "$sched_dir" \
         | grep -q "sched/preempt -> sched/resume" \
    && echo "SCHED_SMOKE=ok" \
    || { echo "SCHED_SMOKE=failed"; dump_dashboard "$sched_dir"; rc=1; }
import glob
import os
import tempfile
import threading
import time

import numpy as np
import pandas as pd

import raydp_tpu.dataframe as rdf
from raydp_tpu import control, telemetry
from raydp_tpu.data import MLDataset
from raydp_tpu.train.spmd_fit import fit_spmd


def factory_builder(ckpt, num_epochs, save_every=0):
    def make_estimator():
        import jax
        import optax

        from raydp_tpu.models import MLP
        from raydp_tpu.parallel import MeshSpec
        from raydp_tpu.train import JAXEstimator

        return JAXEstimator(
            model=MLP(hidden=(16,), out_dim=1), optimizer=optax.adam(3e-2),
            loss="mse", num_epochs=num_epochs, batch_size=128,
            feature_columns=["a", "b"], label_column="y",
            mesh=MeshSpec(dp=len(jax.devices())), seed=0, shuffle=False,
            epoch_mode="stream", checkpoint_dir=ckpt,
            save_every_steps=save_every,
        )

    return make_estimator


def dataset(n):
    rng = np.random.default_rng(0)
    a, b = rng.standard_normal(n), rng.standard_normal(n)
    pdf = pd.DataFrame({"a": a, "b": b, "y": 2 * a - 3 * b + 1})
    return MLDataset.from_df(rdf.from_pandas(pdf, num_partitions=2),
                             num_shards=1)


ds = dataset(4096)
arrival_ds = dataset(512)  # materialized up front: no ETL in the race
# Retention off for the victim so the emergency ckpt survives to the
# end of the (checkpoint-heavy) run for the glob assert below.
env = {"JAX_PLATFORMS": "cpu", "RAYDP_TPU_CKPT_KEEP": "0"}
root = tempfile.mkdtemp()
clean = fit_spmd(
    factory_builder(os.path.join(root, "clean"), 8, save_every=2), ds,
    world_size=1, env=env, timeout=300,
)

control.configure(capacity=1, admit_timeout_s=240.0)
victim_dir = os.path.join(root, "victim")
victim_out = {}


def run_victim():
    with telemetry.job_scope(telemetry.mint_job("victim", priority=0)):
        victim_out["res"] = fit_spmd(
            factory_builder(victim_dir, 8, save_every=2), ds,
            world_size=1, env=env, timeout=300, checkpoint_dir=victim_dir,
        )


vt = threading.Thread(target=run_victim, daemon=True)
vt.start()
# Arrival goes in only once the victim is visibly mid-epoch (first
# periodic checkpoint committed): the preemption must exercise the
# drain, not a startup race.
deadline = time.monotonic() + 240.0
mid = os.path.join(victim_dir, "step_mid_2", "_METADATA")
while time.monotonic() < deadline and not os.path.isfile(mid):
    time.sleep(0.05)
assert os.path.isfile(mid), "victim never reached its first mid ckpt"

_t_arr = time.monotonic()
with telemetry.job_scope(telemetry.mint_job("arrival", priority=5)):
    arrival = fit_spmd(
        factory_builder(None, 1), arrival_ds, world_size=1,
        env={"JAX_PLATFORMS": "cpu"}, timeout=300,
    )
arrival_elapsed = time.monotonic() - _t_arr
vt.join(300.0)
victim = victim_out["res"]

assert arrival["restarts"] == 0, arrival["restarts"]
assert victim["restarts"] == 1, victim["restarts"]
assert glob.glob(os.path.join(victim_dir, "step_emergency_*")), \
    "preemption did not drain an emergency checkpoint"
np.testing.assert_allclose(
    victim["history"][-1]["train_loss"],
    clean["history"][-1]["train_loss"], rtol=1e-4,
)

# Stamp the preempt->resume MTTR the timeline CLI renders below (the
# episode lives in the training subprocess's event shards).
from raydp_tpu.telemetry import events as events_mod

records = events_mod.load_event_records(os.environ["RAYDP_TPU_TELEMETRY_DIR"])
mttrs = [
    ep["repair_s"]
    for job in events_mod.mttr_report(records).values()
    for ep in job.get("episodes", [])
    if ep.get("start_kind") == "sched/preempt"
    and ep.get("end_kind") == "sched/resume"
]
exec(open("scripts/verify_metrics.py").read())
stamp("sched_smoke", {
    "preempt_mttr_s": max(mttrs) if mttrs else -1.0,
    "arrival_epochs_per_sec": len(arrival["history"]) / arrival_elapsed,
})
PYEOF
  rm -rf "$sched_dir"
fi
# Serving smoke (HARD): a replica group under concurrent traffic with
# an injected replica kill must reply to every accepted request
# exactly once (zero drops), keep batches usefully full, and self-heal
# back to full strength — the end-to-end proof of doc/serving.md's
# zero-dropped-request failover story, not just its unit tests.
if [ "$rc" -eq 0 ]; then
  echo "--- serving smoke (replica kill under traffic) ---"
  JAX_PLATFORMS=cpu RAYDP_TPU_FAULT_PLAN="serve_kill:replica=0,request=5" \
    python - <<'PYEOF' \
    && echo "SERVE_SMOKE=ok" \
    || { echo "SERVE_SMOKE=failed"; dump_dashboard; rc=1; }
import threading
import time

from raydp_tpu.serve import ReplicaGroup
from raydp_tpu.utils.profiling import metrics


def make_model():
    # Nested so cloudpickle ships it by value to the replica procs.
    def model(payloads, bucket):
        time.sleep(0.002)
        return [float(sum(p)) for p in payloads]

    return model


N, PER = 240, 30
results = [None] * N
errors = []
_t0 = time.monotonic()

with ReplicaGroup(
    replicas=2, model_fn=make_model(), label="smoke-serve",
    max_batch=4, slo_ms=25, max_queue=N + 16, restart_backoff_s=0.2,
).start() as group:

    def client(base):
        reqs = [
            (i, group.submit([i % 5] * 8, timeout_s=120.0))
            for i in range(base, base + PER)
        ]
        for i, req in reqs:
            try:
                results[i] = req.wait(timeout=120.0)
            except Exception as exc:  # any drop/cancel fails the gate
                errors.append((i, repr(exc)))

    threads = [
        threading.Thread(target=client, args=(b,))
        for b in range(0, N, PER)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    # Self-heal: the killed lineage must respawn back to full strength.
    deadline = time.monotonic() + 30.0
    while time.monotonic() < deadline:
        stats = group.stats()
        if stats["restarts"] >= 1 and stats["replicas_alive"] == 2:
            break
        time.sleep(0.1)

assert not errors, errors[:3]
assert results == [float((i % 5) * 8) for i in range(N)], \
    "replies diverged"
assert stats["restarts"] >= 1, stats
assert stats["replicas_alive"] == 2, stats
assert stats["replies"] == N and stats["errors"] == 0, stats
snap = metrics.snapshot()["counters"]
fill = snap["serve/batch_requests"] / (snap["serve/batches"] * 4)
assert fill > 0.5, (fill, snap)

exec(open("scripts/verify_metrics.py").read())
stamp("serve_smoke", {
    "replies_per_sec": N / (time.monotonic() - _t0),
    "batch_fill": fill,
    "restarts": stats["restarts"],
})
PYEOF
fi
# Decode smoke (HARD): a decode-mode replica group streaming causal-LM
# tokens under concurrent traffic. Three acts against ONE live group:
# a serve_kill lands mid-decode and every in-flight sequence must
# finish token-identical to the in-process reference (the requeue-as-
# prefill recipe, zero drops); then, warm, the same prompts run
# batched vs one-request-at-a-time and continuous batching must clear
# 3x the sequential tokens/s — the end-to-end proof of
# doc/serving.md's iteration-level scheduling story.
if [ "$rc" -eq 0 ]; then
  echo "--- decode smoke (continuous batching + replica kill mid-decode) ---"
  JAX_PLATFORMS=cpu RAYDP_TPU_FAULT_PLAN="serve_kill:replica=0,request=4" \
    python - <<'PYEOF' \
    && echo "DECODE_SMOKE=ok" \
    || { echo "DECODE_SMOKE=failed"; dump_dashboard; rc=1; }
import time

from raydp_tpu.serve import ReplicaGroup
from raydp_tpu.serve.decode import build_transformer_engine
from raydp_tpu.utils.profiling import metrics

# Same factory the replica rebuilds from (seed-pinned init), so the
# driver-side reference decodes with byte-identical weights.
reference = build_transformer_engine(seed=0)

with ReplicaGroup(
    replicas=1, model_fn=build_transformer_engine, label="smoke-decode",
    mode="decode", restart_backoff_s=0.2, max_restarts=3,
    max_queue=64,
).start() as group:
    # Act 1 — kill mid-decode. The fault clause trips on the FIFTH
    # admission (request=4): the first wave of four is already
    # streaming when the trigger lands, so the driver must requeue
    # four live sequences as prefills of their generated-so-far
    # context onto the respawned replica.
    wave = [[i + 1, i + 2, i + 3] for i in range(4)]
    reqs = [group.submit_generate(p, max_new=48, timeout_s=240.0)
            for p in wave]
    deadline = time.monotonic() + 180.0
    while time.monotonic() < deadline:
        if metrics.snapshot()["counters"].get("decode/tokens", 0) >= 4:
            break
        time.sleep(0.01)
    trigger = group.submit_generate([9, 9], max_new=4, timeout_s=240.0)
    for p, r in zip(wave, reqs):
        assert r.wait(timeout=240.0)["tokens"] == \
            reference.reference_decode(p, 48), f"stream diverged for {p}"
    assert trigger.wait(timeout=240.0)["tokens"] == \
        reference.reference_decode([9, 9], 4), "trigger stream diverged"
    mid = group.stats()
    assert mid["restarts"] >= 1, mid
    assert mid["decode"]["requeued_prefills"] >= 1, mid

    # Self-heal before timing: the killed lineage back at strength.
    deadline = time.monotonic() + 60.0
    while time.monotonic() < deadline:
        if group.stats()["replicas_alive"] == 1:
            break
        time.sleep(0.1)
    assert group.stats()["replicas_alive"] == 1, group.stats()

    # Act 2 — batched: 16 concurrent streams over 8 KV slots (the
    # second eight join mid-stream as the first wave retires). The
    # respawned replica is warm by now, so this times scheduling, not
    # XLA.
    prompts = [[(i % 7) + 1, 2, 3, 4] for i in range(16)]
    t0 = time.monotonic()
    breqs = [group.submit_generate(p, max_new=32, timeout_s=240.0)
             for p in prompts]
    batched = [r.wait(timeout=240.0) for r in breqs]
    batched_wall = time.monotonic() - t0
    ttfts = sorted(r.ttft_s() for r in breqs)
    assert all(t is not None for t in ttfts), ttfts

    # Act 3 — the same prompts one-request-at-a-time: the replica's
    # round cost is fixed by its slot batch, so serving sequentially
    # wastes it.
    t0 = time.monotonic()
    seq = [group.generate(p, max_new=32, timeout_s=240.0)
           for p in prompts]
    seq_wall = time.monotonic() - t0

    for i, (b, s) in enumerate(zip(batched, seq)):
        assert b["tokens"] == s["tokens"], \
            f"batched/sequential streams diverged for prompt {i}"
    stats = group.stats()

tokens = sum(len(b["tokens"]) for b in batched)
tps_batched = tokens / batched_wall
tps_seq = tokens / seq_wall
assert tps_batched >= 3.0 * tps_seq, (tps_batched, tps_seq)
assert stats["errors"] == 0, stats
assert stats["replies"] == 5 + 16 + 16, stats
ttft_p99 = ttfts[min(len(ttfts) - 1, int(0.99 * len(ttfts)))]

exec(open("scripts/verify_metrics.py").read())
stamp("decode_smoke", {
    "decode_tokens_per_sec": tps_batched,
    "sequential_tokens_per_sec": tps_seq,
    "speedup_vs_sequential": tps_batched / tps_seq,
    "ttft_p99_s": ttft_p99,
})
PYEOF
fi
# Autoscale smoke (HARD): sustained admission pressure grows a real
# worker pool within ONE evaluation, the injected spawn_fail:nth=1 is
# backed off and retried to convergence, idle drains the pool back to
# min_workers with zero flap episodes (every grow strictly precedes
# every shrink), a scale-down mid-ETL loses no tasks (result parity),
# and every decision is reconstructible from autoscale/* events via
# the timeline CLI — the end-to-end proof of doc/scheduling.md's
# autoscaling story.
if [ "$rc" -eq 0 ]; then
  echo "--- autoscale smoke (pressure grow / chaos spawn / graceful drain) ---"
  as_dir=$(mktemp -d)
  JAX_PLATFORMS=cpu RAYDP_TPU_TELEMETRY_DIR="$as_dir" \
    RAYDP_TPU_FAULT_PLAN="spawn_fail:nth=1" python - <<'PYEOF' \
    && as_tl=$(JAX_PLATFORMS=cpu python -m raydp_tpu.telemetry.events "$as_dir") \
    && grep -q "autoscale/decision" <<<"$as_tl" \
    && grep -q "autoscale/spawn_failed" <<<"$as_tl" \
    && echo "AUTOSCALE_SMOKE=ok" \
    || { echo "AUTOSCALE_SMOKE=failed"; dump_dashboard "$as_dir"; rc=1; }
import threading
import time

import raydp_tpu
from raydp_tpu import control, telemetry
from raydp_tpu.control import (
    Autoscaler,
    AutoscalerConfig,
    ClusterProvisioner,
)
from raydp_tpu.telemetry import events as events_mod
from raydp_tpu.utils.profiling import metrics

session = raydp_tpu.init(app_name="autoscale-smoke", num_workers=1,
                         memory_per_worker="256MB")
cluster = session.cluster
sc = Autoscaler(ClusterProvisioner(cluster), AutoscalerConfig(
    min_workers=1, max_workers=3, interval_s=0.5, up_cooldown_s=0.3,
    down_cooldown_s=0.6, idle_evals=2, spawn_retries=3, backoff_s=0.2,
))

# -- phase 1: sustained admission pressure -> grow within ONE eval.
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
while time.monotonic() < deadline and arb.report()["queue_depth"] != 1:
    time.sleep(0.02)
assert arb.report()["queue_depth"] == 1, arb.report()

_t_grow = time.monotonic()
d = sc.step()  # one evaluation under pressure must already grow
time_to_grow = time.monotonic() - _t_grow
assert d.verdict == "grow", d
assert len(cluster.alive_workers()) == 2

# -- phase 2: second grow trips spawn_fail:nth=1 -> backoff, retry,
# converge (chaos-hardened provisioning).
time.sleep(0.35)  # clear the up-cooldown
_t_grow = time.monotonic()
d = sc.step()
time_to_grow_retry = time.monotonic() - _t_grow
assert d.verdict == "grow", d
assert len(cluster.alive_workers()) == 3
snap = metrics.snapshot()["counters"]
assert snap.get("autoscale/spawn_failed", 0) == 1, snap

holder.release()
wt.join(30.0)
waiter_out["lease"].release()


# -- phase 3: scale-down mid-ETL loses no tasks (result parity).
def task(ctx, i):
    time.sleep(0.15)
    return i


items = list(range(96))
etl_out = {"res": []}


def etl():
    for base in range(0, len(items), 8):  # sequential rounds keep the
        etl_out["res"].extend(            # job in flight across drains
            cluster.map_tasks(task, items[base:base + 8], timeout=120.0)
        )


_t_etl = time.monotonic()
et = threading.Thread(target=etl, daemon=True)
et.start()
time.sleep(0.3)  # tasks in flight on all three workers
_t_drain = time.monotonic()
deadline = time.monotonic() + 60.0
while time.monotonic() < deadline and len(cluster.alive_workers()) > 1:
    sc.step()
    time.sleep(0.25)
drain_s = time.monotonic() - _t_drain
assert len(cluster.alive_workers()) == 1, cluster.alive_workers()
et.join(180.0)
etl_elapsed = time.monotonic() - _t_etl
assert etl_out["res"] == items, "tasks lost in scale-down"

# -- phase 4: zero flap episodes — all grows strictly precede all
# shrinks in the decision record.
acted = [d.verdict for d in sc.decisions
         if d.verdict in ("grow", "shrink")]
assert acted == ["grow", "grow", "shrink", "shrink"], acted

# -- phase 5: every non-steady decision is on the event timeline.
decided = [r for r in events_mod.local_events()
           if r["name"] == "autoscale/decision"]
assert len(decided) == len(
    [d for d in sc.decisions if d.verdict != "steady"]
), (len(decided), [d.verdict for d in sc.decisions])

raydp_tpu.stop()

exec(open("scripts/verify_metrics.py").read())
stamp("autoscale_smoke", {
    "time_to_grow_s": time_to_grow,
    "time_to_grow_retry_s": time_to_grow_retry,
    "drain_s": drain_s,
    "etl_tasks_per_sec": len(items) / etl_elapsed,
})
PYEOF
  rm -rf "$as_dir"
fi
# Observability smoke (HARD): an injected serve latency fault must
# drive the full SLO loop live — the time-series sampler sees the p99
# spike, the engine opens a breach within one evaluation window with
# the offending series and correlated timeline events attached,
# traffic dilution recovers it with a measured MTTR, the episode is a
# first-class MTTR entry, the raydp_slo_* families render it, and the
# dashboard CLI reconstructs it offline from the gate's event shards —
# the end-to-end proof of doc/telemetry.md's SLO engine story.
if [ "$rc" -eq 0 ]; then
  echo "--- observability smoke (SLO breach -> triage -> recovery) ---"
  obs_dir=$(mktemp -d)
  JAX_PLATFORMS=cpu RAYDP_TPU_TELEMETRY_DIR="$obs_dir" \
    RAYDP_TPU_FAULT_PLAN="latency:nth=0,delay=0.8,replica=0" \
    python - <<'PYEOF' \
    && obs_cli=$(JAX_PLATFORMS=cpu python -m raydp_tpu.telemetry.dashboard "$obs_dir") \
    && grep -q "slo/breach" <<<"$obs_cli" \
    && grep -q "slo/recovered" <<<"$obs_cli" \
    && echo "OBS_SMOKE=ok" \
    || { echo "OBS_SMOKE=failed"; dump_dashboard "$obs_dir"; rc=1; }
import time

from raydp_tpu.serve import ReplicaGroup
from raydp_tpu.telemetry import events as events_mod
from raydp_tpu.telemetry import render_prometheus
from raydp_tpu.telemetry.slo import SloConfig, SloEngine, default_objectives
from raydp_tpu.telemetry.timeseries import TimeSeriesConfig, TimeSeriesSampler
from raydp_tpu.utils.profiling import metrics


def make_model():
    # Nested so cloudpickle ships it by value to the replica procs.
    def model(payloads, bucket):
        return [float(sum(p)) for p in payloads]

    return model


sampler = TimeSeriesSampler(config=TimeSeriesConfig(
    interval_s=0.05, capacity=512, max_series=512,
))
engine = SloEngine(
    store=sampler.store,
    config=SloConfig(
        interval_s=0.05, short_window_s=1.0, long_window_s=6.0,
        budget=0.2, burn_threshold=1.0, recovery_evals=2,
    ),
    objectives=[o for o in default_objectives() if o.name == "serve_p99"],
)
_t0 = time.monotonic()
with ReplicaGroup(
    replicas=1, model_fn=make_model(), label="obs-smoke",
    max_batch=1, slo_ms=10_000, restart_backoff_s=0.1,
).start() as group:
    group.predict([1, 2, 3])  # the armed clause stalls this 0.8 s
    t_fault = time.monotonic()
    breach = None
    deadline = time.monotonic() + 20.0
    while time.monotonic() < deadline and breach is None:
        sampler.sample()
        for tr in engine.evaluate():
            if tr["kind"] == "breach":
                breach = tr
        time.sleep(0.05)
    assert breach is not None, "no breach within the evaluation window"
    breach_detect_s = time.monotonic() - t_fault
    attrs = breach["event"]["attrs"]
    assert any(
        r["series"] == "serve/latency/p99_s" for r in attrs["top_series"]
    ), attrs
    assert isinstance(attrs["correlated"], list), attrs

    for i in range(150):  # dilute the rolling p99 below the spike
        group.predict([i, i])
    recovered = None
    deadline = time.monotonic() + 30.0
    while time.monotonic() < deadline and recovered is None:
        sampler.sample()
        for tr in engine.evaluate():
            if tr["kind"] == "recovered":
                recovered = tr
        time.sleep(0.05)
    assert recovered is not None, "no recovery within deadline"
    assert recovered["mttr_s"] > 0

report = events_mod.mttr_report(events_mod.local_events())
assert any(
    ep.get("start_kind") == "slo/breach"
    and ep.get("end_kind") == "slo/recovered"
    for job in report.values() for ep in job.get("episodes", [])
), report
text = render_prometheus(
    {"workers": {}, "aggregate": {}, "driver": metrics.snapshot()}
)
for family in ("raydp_slo_breaches_total", "raydp_slo_status",
               "raydp_slo_burn_rate"):
    assert family in text, family
stats = sampler.store.stats()
assert stats["memory_bytes_est"] < 32 * 1024 * 1024, stats

exec(open("scripts/verify_metrics.py").read())
stamp("obs_smoke", {
    "breach_detect_s": breach_detect_s,
    "slo_mttr_s": recovered["mttr_s"],
    "samples_per_sec": stats["samples"] / (time.monotonic() - _t0),
})
PYEOF
  rm -rf "$obs_dir"
fi
# Load smoke (HARD): the load observatory measured against a live
# replica group — a short open-loop ramp must find a FINITE capacity
# knee (saturated, not a ramp-ceiling artifact), a probe step at 50%
# of that knee must complete with zero non-shed errors, every
# completed request's queue_wait+linger+execute+reply decomposition
# must sum to its end-to-end wall within 5%, and the offline CLI must
# reconstruct the knee curve from the raw results JSONL — the
# end-to-end proof of doc/serving.md's load-observatory story.
if [ "$rc" -eq 0 ]; then
  echo "--- load smoke (knee ramp + phase provenance + offline report) ---"
  load_dir=$(mktemp -d)
  JAX_PLATFORMS=cpu RAYDP_TPU_LOADGEN_RESULTS="$load_dir/results.jsonl" \
    python - <<'PYEOF' \
    && load_cli=$(JAX_PLATFORMS=cpu python -m raydp_tpu.loadgen report "$load_dir/results.jsonl") \
    && grep -q "knee: .* rps (saturated" <<<"$load_cli" \
    && grep -q "phase breakdown" <<<"$load_cli" \
    && echo "LOAD_SMOKE=ok" \
    || { echo "LOAD_SMOKE=failed"; dump_dashboard; rc=1; }
import os
import time

from raydp_tpu.loadgen import (
    GroupTarget, KneeConfig, find_knee, poisson_schedule, run_schedule,
    write_results,
)
from raydp_tpu.serve import ReplicaGroup


def make_model():
    # Nested so cloudpickle ships it by value to the replica procs.
    def model(payloads, bucket):
        time.sleep(0.012)
        return [float(sum(p)) for p in payloads]

    return model


# max_batch=1 + ~12ms model pins capacity near 2/0.012 ~ 170 rps so
# the cliff lands inside a short ramp; tiny linger keeps the knee
# about execute capacity, not the batching window.
config = KneeConfig(
    start_rps=16.0, max_rps=1024.0, step_factor=2.0,
    step_duration_s=1.0, slo_ms=150.0, shed_threshold=0.05,
    bisect_rounds=2, timeout_s=5.0, seed=0,
)
with ReplicaGroup(
    replicas=2, model_fn=make_model(), label="smoke-load",
    max_batch=1, slo_ms=5, max_queue=512, restart_backoff_s=0.2,
).start() as group:
    deadline = time.monotonic() + 30.0
    while group.stats()["replicas_alive"] < 2:
        assert time.monotonic() < deadline, "replicas never came up"
        time.sleep(0.02)
    group.predict([0] * 8, timeout_s=30.0)  # warm dispatch path
    target = GroupTarget(group)
    result = find_knee(target, config)
    # Probe step at 50% of the knee: comfortably below capacity, so
    # nothing may shed, time out, or error.
    probe = run_schedule(
        target,
        poisson_schedule(
            max(1.0, 0.5 * result.knee_rps), 1.5, seed=101
        ),
        timeout_s=config.timeout_s,
    )
    probe80 = run_schedule(
        target,
        poisson_schedule(
            max(1.0, 0.8 * result.knee_rps), 1.5, seed=202
        ),
        timeout_s=config.timeout_s,
    )

# Finite knee: the ramp confirmed a cliff rather than running off the
# top of the sweep.
assert result.saturated, result.summary()
assert 0 < result.knee_rps < config.max_rps, result.summary()

counts = probe.counts()
assert counts["ok"] == len(probe.outcomes) and counts["ok"] > 0, counts

# Latency provenance: the four additive phases reconstruct each
# request's accept->reply wall exactly, and that wall accounts for
# the client-observed end-to-end latency within 5% (plus 10ms
# absolute slack — submit admission + waiter-thread wakeup live
# outside the queue's window and jitter on a loaded CI box).
decomposed = 0
for out in probe.outcomes + probe80.outcomes:
    if out.status != "ok" or not out.phases:
        continue
    decomposed += 1
    phase_sum = sum(
        out.phases[k]
        for k in ("queue_wait", "linger", "execute", "reply")
    )
    assert abs(phase_sum - out.phases["total"]) <= 1e-6, out.phases
    gap = out.latency_s - phase_sum
    assert gap >= -0.001, (phase_sum, out.latency_s)
    assert gap <= max(0.05 * out.latency_s, 0.010), (
        phase_sum, out.latency_s, out.phases
    )
assert decomposed > 0, "no request carried a phase decomposition"

fractions = probe.phase_fractions()
additive = sum(
    fractions.get(k, 0.0)
    for k in ("queue_wait", "linger", "execute", "reply")
)
assert abs(additive - 1.0) <= 0.05, fractions

write_results(os.environ["RAYDP_TPU_LOADGEN_RESULTS"], result)

p99_80 = probe80.latency_quantile(0.99)
exec(open("scripts/verify_metrics.py").read())
stamp("load_smoke", {
    "knee_rps": result.knee_rps,
    "p99_at_knee_ms": (
        result.p99_at_knee_s * 1e3
        if result.p99_at_knee_s is not None else None
    ),
    "p99_at_80pct_knee_ms": (
        p99_80 * 1e3 if p99_80 is not None else None
    ),
    "probe_ok": counts["ok"],
    "phase_sum_checked": decomposed,
})
PYEOF
  rm -rf "$load_dir"
fi
# Sim smoke (HARD): the virtual-clock observatory (doc/simulation.md)
# — a seeded 100k-arrival diurnal+flash trace (round-tripped through
# the loadgen JSONL format) replays through the REAL
# arbiter/autoscaler/serve-queue on virtual time in seconds of wall
# clock with zero invariant violations and zero pathologies; a
# deliberately undersized pool under the same flash crowd must trip
# the shed-storm detector; and the virtual knee over the LOAD_SMOKE
# topology must agree with the real knee that gate just measured
# within 25% — the proof that the simulator predicts the same cliff
# the hardware shows.
if [ "$rc" -eq 0 ]; then
  echo "--- sim smoke (virtual-clock replay + pathology + knee cross-check) ---"
  sim_dir=$(mktemp -d)
  JAX_PLATFORMS=cpu RAYDP_TPU_SIM_TRACE_DIR="$sim_dir" \
    python - <<'PYEOF' \
    && echo "SIM_SMOKE=ok" \
    || { echo "SIM_SMOKE=failed"; rc=1; }
import json
import os

from raydp_tpu.loadgen.knee import KneeConfig
from raydp_tpu.loadgen.schedules import (
    TraceEvent, diurnal_schedule, flash_crowd_schedule,
)
from raydp_tpu.loadgen.trace import read_trace, write_trace
from raydp_tpu.sim import ScenarioConfig, run_trace, sim_knee

# Seeded 100k-arrival trace: a diurnal day with a flash crowd riding
# on top of it, round-tripped through the loadgen JSONL format so the
# sim consumes exactly what the real replay harness would.
diurnal = diurnal_schedule(1200.0, 70.0, seed=1)
flash = flash_crowd_schedule(500.0, 30.0, seed=2, burst_mult=8.0)
events = list(diurnal) + [
    TraceEvent(t=e.t + 70.0, bucket=e.bucket, size=e.size)
    for e in flash
]
assert len(events) >= 100_000, len(events)
trace_path = os.path.join(os.environ["RAYDP_TPU_SIM_TRACE_DIR"],
                          "smoke.jsonl")
write_trace(trace_path, events)
events = read_trace(trace_path)

healthy = run_trace(events, ScenarioConfig(
    hosts=16, max_batch=8, max_queue=4096, slo_ms=250.0,
))
assert healthy.completed == healthy.arrivals, (
    healthy.arrivals, healthy.completed, healthy.shed, healthy.errors
)
assert healthy.invariant_violations == [], healthy.invariant_violations
assert healthy.pathologies == [], healthy.pathologies
assert healthy.wall_s < 60.0, healthy.wall_s

# The same flash crowd over a deliberately undersized pool must trip
# the shed-storm detector — the positive control for the pathology
# plane.
storm = run_trace(flash, ScenarioConfig(
    hosts=1, max_batch=2, max_queue=64, slo_ms=50.0,
))
storm_kinds = {p["kind"] for p in storm.pathologies}
assert "shed_storm" in storm_kinds, storm.pathologies

# Virtual knee over the LOAD_SMOKE topology (2 replicas, batch 1,
# 12ms/call, tiny linger): must land within 25% of the real knee the
# load-smoke gate just measured on the same shape.
knee = sim_knee(
    ScenarioConfig(hosts=2, max_batch=1, service_ms=12.0, slo_ms=5.0,
                   max_queue=512, timeout_s=5.0),
    KneeConfig(start_rps=16.0, max_rps=1024.0, step_factor=2.0,
               step_duration_s=1.0, slo_ms=150.0, shed_threshold=0.05,
               bisect_rounds=2, timeout_s=5.0, seed=0),
)
assert knee["saturated"], knee

real_knee = None
metrics_path = os.environ.get("VERIFY_METRICS_PATH")
if metrics_path and os.path.exists(metrics_path):
    with open(metrics_path) as f:
        doc = json.load(f)
    real_knee = (doc.get("configs", {})
                    .get("load_smoke", {})
                    .get("knee_rps"))
if real_knee:
    gap = abs(knee["knee_rps"] - real_knee) / real_knee
    assert gap <= 0.25, (
        f"sim knee {knee['knee_rps']} vs real {real_knee} rps: "
        f"{gap:.0%} apart (tolerance 25%)"
    )
else:
    gap = None
    print("sim smoke: no load_smoke stamp found; knee cross-check "
          "skipped (standalone run)")

exec(open("scripts/verify_metrics.py").read())
stamp("sim_smoke", {
    "arrivals": healthy.arrivals,
    "wall_s": round(healthy.wall_s, 2),
    "events_per_sec": round(healthy.events_per_s, 1),
    "invariant_violations": len(healthy.invariant_violations),
    "pathologies_healthy": len(healthy.pathologies),
    "shed_storm_detected": 1 if "shed_storm" in storm_kinds else 0,
    "knee_rps": knee["knee_rps"],
    "real_knee_rps": real_knee,
    "knee_gap_frac": round(gap, 4) if gap is not None else None,
})
PYEOF
  rm -rf "$sim_dir"
fi
exit $rc
