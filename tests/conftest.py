"""Test configuration: run JAX on a virtual 8-device CPU mesh.

The reference tests against a real local Ray cluster in two client modes
(reference: python/raydp/tests/conftest.py:34-59). Here the equivalent
"real runtime on one host" is: XLA CPU backend forced to expose 8 devices so
multi-chip collectives (psum over dp, ring attention over sp, tensor-parallel
matmuls over tp) execute for real in every test, without TPU hardware.

Production code never imports this — only pytest does.
"""
import os

# Must be set before jax (transitively) imports: jax reads JAX_PLATFORMS
# and XLA parses XLA_FLAGS once, at first backend init. Hard-set (not
# setdefault): on a host with a chip the tests must not grab it — one
# real chip can't host 8-device mesh tests — and the child processes the
# tests start (workers, ranks, replicas) inherit the same environment.
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("RAYDP_TPU_TEST_MODE", "1")

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def eight_cpu_devices():
    import jax

    devices = jax.devices()
    assert len(devices) >= 8, f"expected >=8 virtual devices, got {len(devices)}"
    return devices


# ---------------------------------------------------------------------
# Dual driver modes. The reference parameterizes EVERY fixture over
# direct and ray:// client connections so its whole suite runs twice
# (reference: python/raydp/tests/conftest.py:42-49). The equivalent
# here: "inprocess" starts the cluster in the test process; "client"
# starts it in a subprocess and attaches the test process as a remote
# gRPC driver (raydp_tpu.connect) — every DataFrame/MLDataset/estimator
# call in the test then rides the client proxies.

_CLIENT_HOST_SCRIPT = """\
import sys
import raydp_tpu

s = raydp_tpu.init(app_name="client-mode-host", num_workers=2)
print("ADDR " + s.cluster.master.address, flush=True)
sys.stdin.read()  # parent closing the pipe is the shutdown signal
raydp_tpu.stop()
"""


@pytest.fixture(scope="module", params=["inprocess", "client"])
def mode_session(request):
    """A live 2-worker session in both driver modes; suites opt in via
    an autouse passthrough fixture (test_estimator / test_ml_dataset /
    test_reverse_path) so every one of their tests runs twice."""
    import subprocess
    import sys as _sys

    import raydp_tpu

    if request.param == "inprocess":
        s = raydp_tpu.init(app_name="mode-inprocess", num_workers=2)
        yield s
        raydp_tpu.stop()
        return

    import select
    import time as _time

    proc = subprocess.Popen(
        [_sys.executable, "-c", _CLIENT_HOST_SCRIPT],
        stdin=subprocess.PIPE,
        stdout=subprocess.PIPE,
        text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )

    def _teardown():
        try:
            proc.stdin.close()
            proc.wait(timeout=30)
        except Exception:
            proc.kill()

    # Bounded wait for the host's ADDR line: a wedged cluster init must
    # fail the fixture, not deadlock the whole pytest run.
    addr = None
    deadline = _time.monotonic() + 120
    buf = ""
    while _time.monotonic() < deadline and proc.poll() is None:
        ready, _, _ = select.select([proc.stdout], [], [], 5)
        if not ready:
            continue
        line = proc.stdout.readline()
        if not line:
            break
        buf += line
        if line.startswith("ADDR "):
            addr = line.split(None, 1)[1].strip()
            break
    if not addr:
        _teardown()
        pytest.fail(
            f"client-mode host cluster failed to start within 120s: {buf!r}"
        )
    try:
        s = raydp_tpu.connect(addr)
    except BaseException:
        _teardown()
        raise
    yield s
    raydp_tpu.stop()
    _teardown()
