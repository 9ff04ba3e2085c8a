"""Self-sizing cluster: autoscaler control loop (doc/scheduling.md).

Unit coverage of the Autoscaler decision machine against a fake
provisioner — grow within one evaluation of pressure, hysteresis and
idle-streak gating on shrink, cooldown denial of direction flips, the
gang-lease floor, spawn-fault backoff/retry/budget-exhaustion, and
bin-packing of freed hosts to waiting serve groups. The end-to-end
path (real Cluster provisioner, real load) is gated by
AUTOSCALE_SMOKE in scripts/verify.sh.
"""
import threading

import pytest

from raydp_tpu import control, fault
from raydp_tpu.control import (
    Autoscaler,
    AutoscalerConfig,
    ClusterProvisioner,
    HostProvisioner,
    ProvisionerError,
)
from raydp_tpu.telemetry import accounting as acct
from raydp_tpu.telemetry import events as events_mod
from raydp_tpu.utils.profiling import metrics as _metrics


@pytest.fixture(autouse=True)
def _clean_state(monkeypatch):
    monkeypatch.delenv("RAYDP_TPU_FAULT_PLAN", raising=False)
    monkeypatch.delenv("RAYDP_TPU_FAULT_SEED", raising=False)
    for var in (v for v in dir(control) if v.startswith("AUTOSCALE")):
        monkeypatch.delenv(getattr(control, var), raising=False)
    fault.reset_for_tests()
    control.reset_for_tests()
    yield
    fault.reset_for_tests()
    control.reset_for_tests()


def _counter(name):
    return _metrics.snapshot().get("counters", {}).get(name, 0)


class FakeProvisioner(HostProvisioner):
    def __init__(self, initial=1, fail_grows=0):
        self._next = initial
        self._hosts = [f"h{i}" for i in range(initial)]
        self.fail_grows = fail_grows
        self.retired = []

    def grow(self, n):
        if self.fail_grows > 0:
            self.fail_grows -= 1
            raise ProvisionerError("no capacity")
        new = []
        for _ in range(n):
            new.append(f"h{self._next}")
            self._next += 1
        self._hosts.extend(new)
        return new

    def retire(self, host_id):
        self._hosts.remove(host_id)
        self.retired.append(host_id)

    def hosts(self):
        return list(self._hosts)


def _scaler(prov, pressure, **cfg_kwargs):
    """Autoscaler with sample_pressure pinned to a mutable cell."""
    defaults = dict(
        min_workers=1, max_workers=4, interval_s=0.05,
        up_cooldown_s=0.0, down_cooldown_s=0.0, idle_evals=1,
        spawn_retries=2, backoff_s=0.01,
    )
    defaults.update(cfg_kwargs)
    sc = Autoscaler(prov, AutoscalerConfig(**defaults))
    cell = {"p": pressure}
    sc.sample_pressure = lambda: dict(cell["p"])  # type: ignore
    return sc, cell


def test_grows_within_one_eval_of_pressure():
    prov = FakeProvisioner(initial=1)
    sc, _ = _scaler(prov, {"sched_queue_depth": 2.0})
    d = sc.step()
    assert d.verdict == "grow" and len(prov.hosts()) == 2
    assert d.signals == {"sched_queue_depth": 2.0}
    gauges = _metrics.snapshot().get("gauges", {})
    assert gauges.get("autoscale/pool_size") == 2.0


def test_idle_streak_gates_shrink():
    prov = FakeProvisioner(initial=2)
    sc, _ = _scaler(prov, {}, idle_evals=3)
    # two idle evals are not enough; the third drains one host
    assert sc.step().verdict == "steady"
    assert sc.step().verdict == "steady"
    d = sc.step()
    assert d.verdict == "shrink" and prov.retired == ["h1"]
    assert len(prov.hosts()) == 1


def test_direction_flip_inside_cooldown_is_denied():
    prov = FakeProvisioner(initial=1)
    sc, cell = _scaler(
        prov, {"sched_queue_depth": 2.0}, down_cooldown_s=60.0
    )
    assert sc.step().verdict == "grow"
    cell["p"] = {}  # pressure vanishes right after the grow
    d = sc.step()
    assert d.verdict == "denied" and "down-cooldown" in d.reason
    assert len(prov.hosts()) == 2  # no flap
    assert _counter("autoscale/denied") >= 1


def test_shrink_never_cuts_below_gang_floor():
    arb = control.configure(capacity=4, admit_timeout_s=5.0)
    lease = arb.acquire(acct.mint_job("fit"), slots=2, kind="gang")
    prov = FakeProvisioner(initial=2)
    sc, _ = _scaler(prov, {})
    assert sc._gang_floor() == 2  # read straight off the arbiter lease
    d = sc.step()
    assert d.verdict == "denied" and "gang floor" in d.reason
    assert prov.retired == []
    lease.release()
    assert sc._gang_floor() == 0
    assert sc.step().verdict == "shrink"  # floor gone, drain proceeds


def test_spawn_fault_backs_off_and_converges(monkeypatch):
    monkeypatch.setenv("RAYDP_TPU_FAULT_PLAN", "spawn_fail:nth=0")
    fault.reset_for_tests()
    prov = FakeProvisioner(initial=1)
    sc, _ = _scaler(prov, {"serve_shed_eta": 3.0})
    before = _counter("autoscale/spawn_failed")
    d = sc.step()
    assert d.verdict == "grow" and len(prov.hosts()) == 2
    assert _counter("autoscale/spawn_failed") == before + 1
    kinds = [r["name"] for r in events_mod.local_events()]
    assert "autoscale/spawn_failed" in kinds
    assert "autoscale/grow" in kinds


def test_spawn_budget_exhaustion_reports_failed():
    prov = FakeProvisioner(initial=1, fail_grows=99)
    sc, _ = _scaler(prov, {"sched_queue_depth": 5.0}, spawn_retries=1)
    before = _counter("autoscale/spawn_failed")
    d = sc.step()
    assert d.verdict == "failed" and "exhausted" in d.reason
    assert len(prov.hosts()) == 1
    assert _counter("autoscale/spawn_failed") == before + 2


def test_freed_host_binpacks_to_waiting_serve_group():
    prov = FakeProvisioner(initial=2)
    sc, _ = _scaler(prov, {})
    taken = []

    def accept(host_id):
        taken.append(host_id)
        prov._hosts.remove(host_id)  # new owner takes the host over
        return True

    sc.request_host("serve-g", accept)
    before = _counter("autoscale/decisions/binpack")
    d = sc.step()
    assert d.verdict == "shrink" and taken == ["h1"]
    assert prov.retired == []  # ownership transferred, not killed
    assert _counter("autoscale/decisions/binpack") == before + 1
    kinds = [r["name"] for r in events_mod.local_events()]
    assert "autoscale/binpack" in kinds


def test_declined_offer_falls_through_to_retire():
    prov = FakeProvisioner(initial=2)
    sc, _ = _scaler(prov, {})
    sc.request_host("picky", lambda host_id: False)
    d = sc.step()
    assert d.verdict == "shrink" and prov.retired == ["h1"]
    assert sc._host_waiters == []  # a declined waiter loses its turn


def test_serve_group_queue_feeds_pressure():
    class Q:
        def depth(self):
            return 16

        def shed_eta_s(self):
            return 0.2

    class G:
        queue = Q()

    sc = Autoscaler(FakeProvisioner(), AutoscalerConfig())
    sc.register_serve_group(G)
    sig = sc.sample_pressure()
    assert sig["serve_queue_depth"] == pytest.approx(2.0)  # 16 / 8
    sc.unregister_serve_group(G)
    assert "serve_queue_depth" not in sc.sample_pressure()


def test_stage_queue_signal_reads_the_measured_queue():
    """``stage_queue`` is the largest ``queue_s`` of the stages recorded
    since the last sample, and ``queue_s`` is ``transit_s + load_s`` of
    the stage's critical envelopes (measured from the stamps on the task
    replies). A stage whose tasks waited for a slot of their worker's
    pool under OTHER bodies has that time in ``exec_s`` — it does not
    trip the signal."""
    from raydp_tpu.telemetry import StageStats, stage_store

    def cluster_stage(wall, transit, load, exec_):
        return StageStats(
            stage_id=0, op="map", executor="cluster", wall_s=wall,
            transit_s=transit, load_s=load, exec_s=exec_,
            queue_s=transit + load, driver_s=wall - transit - load - exec_,
        )

    sc = Autoscaler(FakeProvisioner(), AutoscalerConfig())
    sc.sample_pressure()  # stages recorded before this sample are old
    # Sixteen 0.5 s bodies through a two-slot pool: 4 s of wall, all of
    # it under some body.
    stage_store.record(cluster_stage(4.1, 0.004, 0.001, 4.0))
    assert sc.sample_pressure()["stage_queue"] == pytest.approx(0.005)
    # A slow data plane: the critical envelope spent 1.5 s fetching its
    # data refs and 0.5 s in transit before a 0.1 s body.
    stage_store.record(cluster_stage(2.2, 0.5, 1.5, 0.1))
    stage_store.record(cluster_stage(0.3, 0.01, 0.01, 0.2))
    assert sc.sample_pressure()["stage_queue"] == pytest.approx(2.0)
    assert "stage_queue" not in sc.sample_pressure()  # nothing new


def test_decision_events_reconstruct_the_timeline():
    prov = FakeProvisioner(initial=1)
    sc, cell = _scaler(prov, {"stage_queue": 2.0})
    sc.step()
    cell["p"] = {}
    sc.step()
    decided = [
        r["attrs"] for r in events_mod.local_events()
        if r["name"] == "autoscale/decision"
    ]
    assert decided and decided[-1]["verdict"] in ("shrink", "denied")
    grow_ev = [d for d in decided if d["verdict"] == "grow"]
    assert grow_ev and grow_ev[-1]["signals"] == {"stage_queue": 2.0}
    assert grow_ev[-1]["size"] == 1 and grow_ev[-1]["target"] == 2


def test_start_stop_runs_loop_and_unblocks_backoff():
    prov = FakeProvisioner(initial=1)
    sc, _ = _scaler(prov, {"sched_queue_depth": 2.0}, interval_s=0.02)
    sc.start()
    deadline = threading.Event()
    deadline.wait(0.3)
    sc.stop()
    assert any(d.verdict == "grow" for d in sc.decisions)
    # stop() during a spawn backoff must not deadlock
    slow = FakeProvisioner(initial=1, fail_grows=99)
    sc2, _ = _scaler(
        slow, {"sched_queue_depth": 2.0},
        spawn_retries=1000, backoff_s=5.0, interval_s=0.01,
    )
    sc2.start()
    threading.Event().wait(0.1)  # let the loop enter the backoff
    sc2.stop()  # returns promptly because backoff waits on _stopping
    assert sc2.decisions and sc2.decisions[-1].verdict == "failed"


def test_cluster_provisioner_wraps_backend_errors():
    class Info:
        worker_id = "w-0"

    class Boom:
        def request_workers(self, n):
            raise RuntimeError("launcher exploded")

        def kill_worker(self, wid):
            raise RuntimeError("already gone")

        def alive_workers(self):
            return [Info()]

    prov = ClusterProvisioner(Boom())
    with pytest.raises(ProvisionerError):
        prov.grow(1)
    with pytest.raises(ProvisionerError):
        prov.retire("w-0")
    assert prov.hosts() == ["w-0"] and prov.pick_victim() == "w-0"


def test_config_from_env(monkeypatch):
    monkeypatch.setenv(control.AUTOSCALE_MIN_ENV, "2")
    monkeypatch.setenv(control.AUTOSCALE_MAX_ENV, "7")
    monkeypatch.setenv("RAYDP_TPU_AUTOSCALE_DOWN_THRESHOLD", "0.1")
    monkeypatch.setenv("RAYDP_TPU_AUTOSCALE_IDLE_EVALS", "bogus")
    cfg = AutoscalerConfig.from_env()
    assert cfg.min_workers == 2 and cfg.max_workers == 7
    assert cfg.down_threshold == 0.1
    assert cfg.idle_evals == 3  # unparsable falls back to default
    with pytest.raises(ValueError):
        Autoscaler(FakeProvisioner(), AutoscalerConfig(
            min_workers=5, max_workers=2,
        ))
