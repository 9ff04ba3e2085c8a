"""Multi-host pod bring-up: one driver pod + N host pods.

The entry point the k8s manifest (deploy/k8s/raydp-tpu-pod.yaml) runs on
every pod of a TPU slice. Pod 0 is the driver: it starts the AppMaster on
a fixed port with num_workers=0 and waits for the other pods' workers to
register over the pod network. Every other pod starts a store agent and
ETL workers for ITS host, pointed at the driver. Once the gang is
registered the driver runs the ETL→train pipeline.

Role parity: the reference's docker/example.yaml + raydp-submit flow
(Ray cluster launcher brings up nodes; Spark executors register with the
AppMaster from every node).

Run (single machine rehearsal):  python examples/pod_driver.py --smoke
"""
import argparse
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

MASTER_PORT = int(os.environ.get("RAYDP_TPU_POD_MASTER_PORT", "43117"))


def run_driver(args):
    import numpy as np
    import pandas as pd

    import raydp_tpu
    import raydp_tpu.dataframe as rdf
    from data_process import nyc_taxi_preprocess, synthetic_taxi

    session = raydp_tpu.init(
        app_name="pod-driver",
        num_workers=0,  # workers join from the host pods
        bind_host=args.bind_host,
        master_port=MASTER_PORT,
    )
    try:
        expected = args.expect_workers
        print(f"driver up @ {session.cluster.master.address}; "
              f"waiting for {expected} workers")
        deadline = time.monotonic() + args.join_timeout
        while time.monotonic() < deadline:
            if len(session.cluster.alive_workers()) >= expected:
                break
            time.sleep(1.0)
        workers = session.cluster.alive_workers()
        assert len(workers) >= expected, f"only {len(workers)} joined"
        print("workers:", [(w.worker_id, w.node_id) for w in workers])

        df = nyc_taxi_preprocess(
            rdf.from_pandas(synthetic_taxi(20_000), num_partitions=8)
        )
        stats = df.groupBy("day_of_week").agg({"fare_amount": "mean"})
        print(stats.to_pandas().sort_values("day_of_week").to_string(index=False))
        print("pod_driver driver OK")
    finally:
        raydp_tpu.stop()


def run_host(args):
    """A host pod: store agent + ETL workers for this node."""
    node_id = args.node_id or os.environ.get("HOSTNAME", "pod-host")
    master = f"{args.driver_host}:{MASTER_PORT}"
    # The agent learns the session namespace from the master at startup.
    procs = [
        subprocess.Popen(
            [
                sys.executable, "-m", "raydp_tpu.store.agent",
                "--node-id", node_id,
                "--master", master,
                "--bind-host", args.bind_host,
            ]
        )
    ]
    for i in range(args.workers_per_host):
        procs.append(
            subprocess.Popen(
                [
                    sys.executable, "-m", "raydp_tpu.cluster.worker_main",
                    "--worker-id", f"{node_id}-w{i}",
                    "--master", master,
                    "--node-id", node_id,
                    "--bind-host", args.bind_host,
                ]
            )
        )
    # Host pods answer the k8s /healthz probes themselves (the driver
    # pod's endpoint comes from Cluster.start): healthy while every
    # child process of this pod is still alive. Workers additionally
    # serve their own per-process endpoints when RAYDP_TPU_DEBUG_PORT
    # is set (use 0 — several workers share this pod).
    server = None
    port = os.environ.get("RAYDP_TPU_METRICS_PORT")
    if port:
        from raydp_tpu.telemetry import serve_prometheus

        def pod_health():
            dead = [p.pid for p in procs if p.poll() is not None]
            return {"healthy": not dead, "dead_children": dead,
                    "node_id": node_id}

        try:
            server = serve_prometheus(
                lambda: "", int(port), health=pod_health
            )
        except Exception:
            print(f"host {node_id}: debug endpoint failed", file=sys.stderr)
    try:
        for p in procs:
            p.wait()
    finally:
        if server is not None:
            server.close()


def run_smoke():
    """Single-machine rehearsal: the same bring-up shape on 2 virtual
    hosts (driver + local workers), then the pipeline."""
    import numpy as np

    import raydp_tpu
    import raydp_tpu.dataframe as rdf
    from data_process import nyc_taxi_preprocess, synthetic_taxi

    session = raydp_tpu.init(
        app_name="pod-smoke", num_workers=2, num_virtual_nodes=2
    )
    try:
        nodes = {w.node_id for w in session.cluster.alive_workers()}
        assert nodes == {"node-0", "node-1"}, nodes
        df = nyc_taxi_preprocess(
            rdf.from_pandas(synthetic_taxi(5_000), num_partitions=4)
        )
        n = df.count()
        assert n > 0
        print(f"pod_driver smoke: {n} rows across {sorted(nodes)}")
        print("pod_driver OK")
    finally:
        raydp_tpu.stop()


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--smoke", action="store_true")
    parser.add_argument("--role", choices=["driver", "host"], default="driver")
    parser.add_argument("--driver-host", default="127.0.0.1")
    parser.add_argument("--bind-host", default="0.0.0.0")
    parser.add_argument("--node-id", default=None)
    parser.add_argument("--workers-per-host", type=int, default=2)
    parser.add_argument("--expect-workers", type=int, default=2)
    parser.add_argument("--join-timeout", type=float, default=300.0)
    args = parser.parse_args()
    if args.smoke:
        run_smoke()
    elif args.role == "driver":
        run_driver(args)
    else:
        run_host(args)


if __name__ == "__main__":
    main()
