"""Query-profiling plane: EXPLAIN / EXPLAIN ANALYZE rendering, stage
runtime-stat invariants, the new Prometheus families, live progress
convergence, and compile-failure enrichment (see doc/telemetry.md,
"Query profiling")."""
import re

import numpy as np
import pandas as pd
import pytest

import raydp_tpu
import raydp_tpu.dataframe as rdf
from raydp_tpu.dataframe import col, dataframe as D
from raydp_tpu.telemetry import render_prometheus
from raydp_tpu.telemetry.progress import stage_store
from raydp_tpu.utils.profiling import metrics


@pytest.fixture()
def zero_coalesce(monkeypatch):
    """Defeat the adaptive coalescers so small test tables exercise
    real multi-partition exchanges instead of single-task collapses."""
    monkeypatch.setattr(D, "_EXCHANGE_COALESCE_BYTES", 0)
    monkeypatch.setattr(D, "_AGG_COALESCE_BYTES", 0)
    monkeypatch.setattr(D, "_COMBINE_COALESCE_BYTES", 0)


def _kv_frame(n=20_000, parts=4, seed=7, keys=16):
    rng = np.random.RandomState(seed)
    return rdf.from_pandas(
        pd.DataFrame({"k": rng.randint(0, keys, n), "v": rng.rand(n)}),
        num_partitions=parts,
    )


def _dlrm_pipeline(df):
    """The DLRM preprocessing idiom: window (forces one exchange on k)
    then groupBy on the SAME key (exchange elided)."""
    w = rdf.Window.partitionBy("k").orderBy("v")
    return (
        df.withColumn("rn", rdf.row_number().over(w))
        .groupBy("k")
        .agg({"v": "max"})
    )


def _footer(text):
    m = re.search(
        r"== Exchanges == ran: (\d+), elided: (\d+), coalesced: (\d+)", text
    )
    assert m, f"no exchange footer in:\n{text}"
    return int(m.group(1)), int(m.group(2)), int(m.group(3))


def _elided_counter():
    return metrics.snapshot().get("counters", {}).get("shuffle/elided", 0.0)


def test_explain_elision_matches_counter(zero_coalesce):
    before = _elided_counter()
    out = _dlrm_pipeline(_kv_frame())
    text = out.explain(analyze=True, quiet=True)
    ran, elided, _ = _footer(text)
    assert ran == 1
    assert elided == 1
    # The plan annotation and the shuffle/elided counter are two views
    # of the same planner decision — they must agree.
    assert _elided_counter() - before == elided
    prom = render_prometheus({"driver": metrics.snapshot()})
    m = re.search(r'raydp_shuffles_elided_total\{[^}]*\} (\d+(\.\d+)?)', prom)
    assert m and float(m.group(1)) >= elided


def test_explain_analyze_dlrm_one_exchange(zero_coalesce):
    text = _dlrm_pipeline(_kv_frame()).explain(analyze=True, quiet=True)
    assert "== Physical Plan ==" in text
    # Exactly ONE exchange node ran (the window's); the groupBy reuses
    # its partitioning.
    exchange_lines = [
        ln for ln in text.splitlines()
        if "hash exchange" in ln and "elided" not in ln
    ]
    assert len(exchange_lines) == 1, text
    assert "exchange elided" in text  # the groupBy side
    # Per-stage stats rendered: rows, bytes, wall seconds, skew.
    stage_lines = [ln for ln in text.splitlines() if "stage " in ln]
    assert stage_lines, text
    for ln in stage_lines:
        assert re.search(r"rows [\d,]+ -> [\d,]+", ln), ln
        assert re.search(r"wall \d+\.\d+s", ln), ln
        skew = float(re.search(r"skew (\d+\.\d+)", ln).group(1))
        assert skew >= 1.0
    assert "[pending]" not in text  # analyze executed the whole plan


def test_explain_logical_plan_is_lazy(zero_coalesce):
    df = _kv_frame().withColumn("v2", col("v") * 2).filter(col("v2") > 0.5)
    text = df.explain(quiet=True)
    assert "== Logical Plan ==" in text
    assert "[pending]" in text  # nothing executed
    assert df.stage_stats == []


def test_narrow_stage_rows_in_equals_rows_out(zero_coalesce):
    df = (
        _kv_frame(n=5000, parts=3)
        .withColumn("v2", col("v") * 2)
        .select("k", "v2")
        ._flush()
    )
    stats = df.stage_stats
    assert stats, "flush recorded no stage stats"
    for s in stats:
        # Narrow ops neither drop nor create rows.
        assert s.rows_in == s.rows_out == 5000
        assert s.parts_in == s.parts_out == 3
        assert s.skew >= 1.0
        assert s.wall_s >= 0.0


def test_stage_stats_skew_reflects_zipf_keys(zero_coalesce):
    rng = np.random.RandomState(3)
    skewed = np.minimum(rng.zipf(1.5, 20_000), 64) - 1
    df = rdf.from_pandas(
        pd.DataFrame({"k": skewed, "v": rng.rand(20_000)}),
        num_partitions=4,
    )
    last0 = stage_store.last_id()
    # A window forces a raw-row hash exchange on k: the head key's mass
    # all lands in one bucket, so the exchange's output partition
    # layout must show real skew.
    w = rdf.Window.partitionBy("k").orderBy("v")
    df.withColumn("rn", rdf.row_number().over(w))._flush()
    stats = [s for s in stage_store.recent(64) if s.stage_id > last0]
    assert stats
    assert max(s.skew for s in stats) > 1.2


def test_new_prometheus_families_render(zero_coalesce):
    from raydp_tpu.utils.profiling import sample_resource_gauges

    _dlrm_pipeline(_kv_frame())._flush()
    sample_resource_gauges()
    prom = render_prometheus({"driver": metrics.snapshot()})
    for family in (
        "raydp_stage_rows_total",
        "raydp_stage_bytes_total",
        "raydp_stage_seconds_total",
        "raydp_host_rss_bytes",
    ):
        assert f"# TYPE {family}" in prom, family
    # Stage counters carry op + direction labels.
    assert re.search(
        r'raydp_stage_rows_total\{[^}]*direction="in"[^}]*op="[^"]+"'
        r'[^}]*\}', prom
    ), prom


def test_stage_stats_kill_switch(zero_coalesce, monkeypatch):
    monkeypatch.setenv("RAYDP_TPU_STAGE_STATS", "0")
    last0 = stage_store.last_id()
    df = _kv_frame(n=2000, parts=2).withColumn("v2", col("v") + 1)._flush()
    assert df.count() == 2000
    assert stage_store.last_id() == last0  # nothing recorded
    assert df.stage_stats == []
    # The plan still renders — just without stats.
    assert "== Physical Plan ==" in df.explain(analyze=True, quiet=True)


def test_compile_error_enrichment():
    from raydp_tpu.train.estimator import _guard_compile
    from raydp_tpu.utils.profiling import CompileError

    # What a local compiler says when a program does not fit the chip.
    compiler_msg = (
        "RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran out of "
        "memory in memory space hbm. Used 21.50G of 15.75G hbm."
    )
    calls = {"n": 0}

    def too_big(x):
        calls["n"] += 1
        raise RuntimeError(compiler_msg)

    before = metrics.snapshot().get("counters", {}).get(
        "compile/failures", 0.0
    )
    # A first-dispatch failure is wrapped with the step label, the
    # compile duration and the compiler's own words — and is raised at
    # once: a compiler diagnostic is deterministic, nothing retries it.
    with pytest.raises(CompileError) as exc_info:
        _guard_compile(too_big, "train_step")(np.ones(4, np.float32))
    assert calls["n"] == 1
    err = exc_info.value
    msg = str(err)
    assert "train_step" in msg
    assert "Ran out of memory in memory space hbm" in msg
    assert re.search(r"after \d+\.\d+s", msg)
    assert err.label == "train_step"
    assert err.duration_s >= 0.0
    assert err.payload_bytes == 16
    assert err.xla_detail == compiler_msg
    assert err.retryable is False
    assert isinstance(err.__cause__, RuntimeError)  # original traceback kept
    after = metrics.snapshot()["counters"]["compile/failures"]
    assert after == before + 1

    def runtime_fail(x):
        if x > 1:
            raise ValueError("nan loss")
        return x

    g2 = _guard_compile(runtime_fail, "eval_step")
    assert g2(1) == 1  # first call (the "compile") succeeds
    with pytest.raises(ValueError, match="nan loss"):
        g2(2)  # later failure passes through un-enriched


# --------------------------------------------------------------- cluster


@pytest.fixture(scope="module")
def session():
    s = raydp_tpu.init(app_name="profiling-test", num_workers=2,
                       memory_per_worker="256MB")
    yield s
    raydp_tpu.stop()


def test_progress_and_analyze_on_cluster(session, zero_coalesce):
    """One cluster round-trip covers both satellite claims: the
    progress report converges after execution, and EXPLAIN ANALYZE
    attributes the same stages to the cluster backend."""
    from raydp_tpu.dataframe.executor import ClusterExecutor

    last0 = stage_store.last_id()
    df = _kv_frame(n=8000, parts=4)
    assert isinstance(df._executor, ClusterExecutor)
    out = _dlrm_pipeline(df)
    text = out.explain(analyze=True, quiet=True)

    ran, elided, _ = _footer(text)
    assert ran == 1 and elided == 1
    assert "[cluster]" in text  # stages attributed to the cluster backend
    assert re.search(r"workers=\d+", text), text

    report = session.cluster.progress_report()
    # Converged: none of THIS query's stages is still in flight, and
    # every one that finished ran all its tasks. (Delta-based: earlier
    # test files share the global tracker.)
    assert [st for st in report["active"] if st["stage_id"] > last0] == []
    mine = [st for st in report["recent"] if st["stage_id"] > last0]
    assert mine, report
    for st in mine:
        assert st["done"] >= st["total"]
    assert report["stages_done"] >= len(mine)
    totals = report["stage_totals"]
    assert totals["stages"] >= 1
    assert totals["rows_out"] >= 16
