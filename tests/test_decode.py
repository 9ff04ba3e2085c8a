"""Autoregressive decode tests: paged KV slot pool, the continuous-
batching round loop, greedy parity against the unbatched reference,
and the zero-drop failover contract at token granularity.

Scheduler-layer tests run on :class:`ToyDecodeEngine` (deterministic
arithmetic, no jit time); the model layer proves the jitted
prefill/decode_step path token-identical to a full no-cache forward;
the end-to-end layer spawns a real decode-mode ReplicaGroup and kills
a replica mid-decode — in-flight sequences must requeue as prefills
and every stream must still match the reference exactly.
"""
import time

import pytest

from raydp_tpu.serve import ReplicaGroup
from raydp_tpu.serve.decode import (
    DecodeConfig,
    DecodeLoop,
    PagedSlotPool,
    ToyDecodeEngine,
    bucket_for,
    kv_buckets,
    reference_decode,
)
from raydp_tpu.utils.profiling import metrics


@pytest.fixture(autouse=True)
def _fresh_metrics():
    metrics.reset()
    yield
    metrics.reset()


def _counter(name):
    return metrics.snapshot()["counters"].get(name, 0)


# ---------------------------------------------------------------------
# kv buckets
# ---------------------------------------------------------------------


def test_kv_buckets_double_geometrically():
    assert kv_buckets(16, 128) == (16, 32, 64, 128)
    assert kv_buckets(16, 100) == (16, 32, 64, 100)
    assert kv_buckets(8, 8) == (8,)


def test_bucket_for_picks_tightest():
    buckets = kv_buckets(16, 128)
    assert bucket_for(buckets, 1) == 16
    assert bucket_for(buckets, 16) == 16
    assert bucket_for(buckets, 17) == 32
    assert bucket_for(buckets, 65) == 128
    # oversize clamps to the last bucket rather than KeyError-ing
    assert bucket_for(buckets, 999) == 128


# ---------------------------------------------------------------------
# PagedSlotPool
# ---------------------------------------------------------------------


def test_pool_allocate_free_churn():
    pool = PagedSlotPool(num_slots=4, page_tokens=16, max_len=128)
    slots = {}
    for i in range(4):
        slots[i] = pool.allocate(f"r{i}", 10 + i * 16)
        assert slots[i] is not None
    assert pool.free_slot_count == 0
    assert pool.allocate("r4", 8) is None  # no slot free
    # free the middle two; re-allocation reuses the LOWEST free slot
    pool.free(slots[2])
    pool.free(slots[1])
    got = pool.allocate("r5", 8)
    assert got == min(slots[1], slots[2])
    assert pool.owner(got) == "r5"
    # churn everything back down to empty: page accounting must zero
    for s in range(4):
        pool.free(s)
    assert pool.used_pages == 0
    assert pool.free_slot_count == 4
    assert pool.page_fill() == 0.0


def test_pool_grow_and_page_backpressure():
    # 4 pages total, 2 slots: two 1-page sequences fit, growth beyond
    # the budget reports False (the loop evicts), and admission past
    # the budget returns None even with a slot free.
    pool = PagedSlotPool(num_slots=2, page_tokens=16, max_len=64,
                         total_pages=3)
    a = pool.allocate("a", 16)   # 1 page
    b = pool.allocate("b", 17)   # 2 pages
    assert a is not None and b is not None
    assert pool.used_pages == 3
    assert not pool.ensure(a, 17)  # budget exhausted → evict signal
    pool.free(b)
    assert pool.ensure(a, 17)      # pages released → growth resumes
    assert pool.used_pages == 2


def test_pool_rejects_oversize_sequence():
    pool = PagedSlotPool(num_slots=2, page_tokens=16, max_len=64)
    with pytest.raises(ValueError):
        pool.allocate("big", 65)


# ---------------------------------------------------------------------
# DecodeLoop scheduling (toy engine: no jit, pure arithmetic)
# ---------------------------------------------------------------------


def _toy_loop(num_slots=4, **cfg):
    engine = ToyDecodeEngine(num_slots=num_slots)
    config = DecodeConfig(slots=num_slots, page_tokens=16,
                          round_linger_s=0.0, **cfg)
    return engine, DecodeLoop(engine, config)


def test_batched_matches_unbatched_reference():
    engine, loop = _toy_loop(num_slots=4)
    prompts = [[i + 1, i + 2, i + 3] for i in range(7)]  # > slots
    for i, p in enumerate(prompts):
        loop.submit(f"r{i}", p, max_new=12)
    loop.run_until_idle()
    for i, p in enumerate(prompts):
        info = loop.sequence_info(f"r{i}")
        assert info is not None and info["reason"] == "length"
        assert info["tokens"] == reference_decode(engine, p, 12)


def test_eos_and_length_retirement():
    engine, loop = _toy_loop(num_slots=2)
    ref = reference_decode(engine, [5, 9], 40)
    eos = ref[3]  # force an early stop on a token we know arrives
    loop.submit("e", [5, 9], max_new=40, eos=eos)
    loop.submit("l", [5, 9], max_new=6)
    loop.run_until_idle()
    assert loop.sequence_info("e")["reason"] == "eos"
    assert loop.sequence_info("e")["tokens"] == ref[:4]
    assert loop.sequence_info("l")["reason"] == "length"
    assert len(loop.sequence_info("l")["tokens"]) == 6


def test_midstream_admission_joins_next_round():
    """A request arriving while the batch is running joins at the very
    next round — it never waits for the batch to drain."""
    engine, loop = _toy_loop(num_slots=4)
    loop.submit("a", [1, 2, 3], max_new=30)
    for _ in range(3):
        loop.run_round()
    assert loop.counts()["live"] == 1  # a is mid-stream
    loop.submit("b", [4, 5, 6], max_new=5)
    loop.run_until_idle()
    info_b = loop.sequence_info("b")
    # submitted after round 3 → admitted exactly at round 4
    assert info_b["admit_round"] == 4
    assert info_b["tokens"] == reference_decode(engine, [4, 5, 6], 5)
    # and the early sequence was not disturbed by the join
    assert loop.sequence_info("a")["tokens"] == \
        reference_decode(engine, [1, 2, 3], 30)


def test_eviction_requeues_prefix_and_stream_is_exact():
    """Page pressure evicts a growing sequence; its generated-so-far
    prefix re-enters as a prefill and the final stream is identical
    to an uncontended run (recompute changes cost, never content)."""
    engine = ToyDecodeEngine(num_slots=4)
    config = DecodeConfig(slots=4, page_tokens=4, round_linger_s=0.0,
                          total_pages=10)
    streams = {}

    def on_token(rid, index, token):
        # a duplicated or skipped global index would corrupt the dict
        assert index == len(streams.setdefault(rid, []))
        streams[rid].append(token)

    loop = DecodeLoop(engine, config, on_token=on_token)
    prompts = [[i + 1, i + 2, i + 3] for i in range(4)]
    for i, p in enumerate(prompts):
        loop.submit(f"r{i}", p, max_new=20)
    loop.run_until_idle()
    snap = metrics.snapshot()["counters"]
    assert snap.get("decode/evictions", 0) >= 1
    for i, p in enumerate(prompts):
        assert streams[f"r{i}"] == reference_decode(engine, p, 20)


def test_cancel_pending_and_live():
    engine, loop = _toy_loop(num_slots=2)
    loop.submit("live", [1, 2], max_new=30)
    loop.run_round()
    loop.submit("pending", [3, 4], max_new=30)
    loop.cancel("live")
    loop.cancel("pending")
    loop.run_round()
    assert loop.sequence_info("live")["reason"] == "cancel"
    assert loop.sequence_info("pending")["reason"] == "cancel"
    assert loop.counts()["live"] == 0
    assert loop.counts()["pending"] == 0


def test_deadline_expiry_retires_with_timeout():
    t = [0.0]
    engine = ToyDecodeEngine(num_slots=2)
    config = DecodeConfig(slots=2, round_linger_s=0.0)
    loop = DecodeLoop(engine, config, clock=lambda: t[0])
    loop.submit("d", [1, 2], max_new=1000, deadline_s=5.0)
    loop.run_round()
    assert loop.counts()["live"] == 1
    t[0] = 6.0
    loop.run_round()
    assert loop.sequence_info("d")["reason"] == "timeout"


def test_round_uses_tightest_kv_bucket():
    engine, loop = _toy_loop(num_slots=2)
    loop.submit("s", [1, 2, 3], max_new=200)
    stats = loop.run_round()
    # 3 prompt positions + 1 next write → the 16-token bucket
    assert stats["kv_bucket"] == 16
    for _ in range(20):
        stats = loop.run_round()
    # cache has grown past one page → bucket doubled, not maxed
    assert stats["kv_bucket"] == 32


def test_submit_validation():
    _, loop = _toy_loop()
    with pytest.raises(ValueError):
        loop.submit("empty", [])
    with pytest.raises(ValueError):
        loop.submit("huge", list(range(200)))  # >= toy max_len 128


# ---------------------------------------------------------------------
# Transformer engine: cached decode must equal the full forward
# ---------------------------------------------------------------------


def test_transformer_greedy_parity_batched_vs_reference():
    """The acceptance bar: greedy decode through the paged cache +
    batched rounds is token-identical to a full no-cache forward per
    token, across ragged prompts admitted together."""
    from raydp_tpu.serve.decode import build_transformer_engine

    engine = build_transformer_engine(num_slots=4, page_tokens=16)
    config = DecodeConfig(slots=4, page_tokens=16, round_linger_s=0.0)
    loop = DecodeLoop(engine, config)
    prompts = [[7, 3, 9], [11, 2], [5, 5, 5, 5, 1], [1]]
    for i, p in enumerate(prompts):
        loop.submit(f"t{i}", p, max_new=8)
    loop.run_until_idle()
    for i, p in enumerate(prompts):
        got = loop.sequence_info(f"t{i}")["tokens"]
        want = reference_decode(engine, p, 8)
        assert got == want, f"prompt {p}: {got} != {want}"


# ---------------------------------------------------------------------
# End to end: decode replica group, kill mid-decode, zero drops
# ---------------------------------------------------------------------


def _toy_reference(prompt, max_new):
    return ToyDecodeEngine().reference_decode(prompt, max_new)


def test_decode_group_streams_and_phases():
    with ReplicaGroup(
        replicas=1, label="t-dec", mode="decode",
        restart_backoff_s=0.1,
    ).start() as group:
        reqs = [
            group.submit_generate([i + 1, i + 2], max_new=6,
                                  timeout_s=30.0)
            for i in range(4)
        ]
        for i, r in enumerate(reqs):
            out = r.wait(timeout=60.0)
            assert out["tokens"] == _toy_reference([i + 1, i + 2], 6)
            assert out["finish_reason"] == "length"
            phases = r.phases
            # prefill + decode is an exact split of execute, and the
            # four primary phases still sum to the wall
            assert phases["prefill"] >= 0
            assert phases["decode"] >= 0
            assert phases["prefill"] + phases["decode"] == \
                pytest.approx(phases["execute"], abs=1e-6)
            assert phases["queue_wait"] + phases["linger"] + \
                phases["execute"] + phases["reply"] == \
                pytest.approx(phases["total"], abs=1e-6)
            assert r.ttft_s() is not None and r.ttft_s() > 0
        stats = group.stats()
        assert stats["mode"] == "decode"
        assert stats["decode"]["tokens"] == 24
        assert stats["decode"]["retired"]["length"] == 4
        assert stats["decode"]["ttft_p50_s"] is not None


def _paced_toy_engine(round_s):
    # Nested so cloudpickle ships it by value — a replica subprocess
    # cannot import this test module by name.
    def factory():
        import time as _time

        from raydp_tpu.serve.decode import ToyDecodeEngine

        class Paced(ToyDecodeEngine):
            def step(self, *args):
                _time.sleep(round_s)
                return super().step(*args)

        return Paced()

    return factory


def test_decode_replica_kill_requeues_as_prefills(monkeypatch):
    """serve_kill lands at a LATER admission (request index 4), so the
    first wave is already streaming tokens when the replica dies. The
    driver must requeue every in-flight sequence as a prefill of its
    generated-so-far context; after respawn every stream must still be
    byte-identical to the reference — zero drops, no duplicated or
    skipped token indices."""
    monkeypatch.setenv(
        "RAYDP_TPU_FAULT_PLAN", "serve_kill:replica=0,request=4"
    )
    with ReplicaGroup(
        replicas=1, label="t-deckill", mode="decode",
        # 64 rounds at 20 ms keep the first wave in flight for over a
        # second, however slowly a loaded host gets the fifth admission
        # to the replica.
        model_fn=_paced_toy_engine(0.02),
        restart_backoff_s=0.1, max_restarts=3,
    ).start() as group:
        prompts = [[i + 1, i + 2, i + 3] for i in range(4)]
        # the counter is the process's, not this group's: read a delta
        before = _counter("decode/tokens")
        reqs = [
            group.submit_generate(p, max_new=64, timeout_s=60.0)
            for p in prompts
        ]
        # wait until the first wave is actually mid-decode
        deadline = time.monotonic() + 20.0
        while time.monotonic() < deadline:
            if _counter("decode/tokens") - before >= 4:
                break
            time.sleep(0.005)
        # the 5th admission trips the kill clause on incarnation 0
        trigger = group.submit_generate([9, 9], max_new=4,
                                        timeout_s=60.0)
        for p, r in zip(prompts, reqs):
            assert r.wait(timeout=60.0)["tokens"] == \
                _toy_reference(p, 64), f"stream diverged for {p}"
        assert trigger.wait(timeout=60.0)["tokens"] == \
            _toy_reference([9, 9], 4)
        stats = group.stats()
        assert stats["restarts"] >= 1, stats
        assert stats["decode"]["requeued_prefills"] >= 1, stats
        assert stats["replies"] == 5, stats
        assert stats["errors"] == 0, stats
