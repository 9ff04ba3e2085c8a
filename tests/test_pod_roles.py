"""The real multi-process pod bring-up: a driver process with
num_workers=0 plus a host process contributing a store agent + workers
over the network (what deploy/k8s/raydp-tpu-pod.yaml runs)."""
import os
import subprocess
import sys
import threading
import time

import pytest

from raydp_tpu.utils.net import find_free_port

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_driver_and_host_roles_cross_process():
    port = find_free_port()
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "RAYDP_TPU_POD_MASTER_PORT": str(port),
    }
    script = os.path.join(REPO, "examples", "pod_driver.py")
    host = subprocess.Popen(
        [
            sys.executable, script, "--role", "host",
            "--driver-host", "127.0.0.1", "--bind-host", "127.0.0.1",
            "--node-id", "pod-1", "--workers-per-host", "2",
        ],
        env=env, cwd=REPO,
        stdout=subprocess.DEVNULL, stderr=subprocess.STDOUT,
    )
    try:
        driver = subprocess.run(
            [
                sys.executable, script, "--role", "driver",
                "--bind-host", "127.0.0.1", "--expect-workers", "2",
                "--join-timeout", "90",
            ],
            env=env, cwd=REPO, capture_output=True, text=True, timeout=240,
        )
        assert driver.returncode == 0, driver.stdout[-2000:] + driver.stderr[-2000:]
        assert "pod_driver driver OK" in driver.stdout
        assert "pod-1" in driver.stdout  # workers joined from the host pod
    finally:
        host.terminate()
        try:
            host.wait(timeout=10)
        except subprocess.TimeoutExpired:
            host.kill()


def test_agent_waits_for_a_master_that_listens_late():
    """A host pod's agent may be up before the driver pod's master:
    it waits for the master to listen, where one refused Ping was fatal."""
    from raydp_tpu.cluster.rpc import RpcServer
    from raydp_tpu.store.agent import StoreAgent

    port = find_free_port()
    namespace = f"late-master-{port}"
    started = []

    def listen():
        started.append(RpcServer(
            "raydp.AppMaster",
            {"Ping": lambda req: {"pong": True, "namespace": namespace}},
            host="127.0.0.1", port=port,
        ))

    # well after the agent's first connection is refused
    late = threading.Timer(1.5, listen)
    late.start()
    agent = None
    try:
        agent = StoreAgent(None, "pod-late", f"127.0.0.1:{port}")
        assert agent.store.namespace == namespace
    finally:
        late.join()
        if agent is not None:
            agent._server.stop()
            agent.store.destroy()
        for server in started:
            server.stop()
