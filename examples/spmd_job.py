"""SPMD gang job: ship a function to every rank, collect results.

Counterpart of the reference's MPI-on-Ray examples (doc/mpi.md,
examples/horovod_nyctaxi.py's allreduce role): a gang of processes with
ranks, a shipped closure, and a collective — here the collective is an
XLA psum over jax.distributed instead of MPI/NCCL.

Run: python examples/spmd_job.py [--smoke]
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from raydp_tpu.spmd import create_spmd_job


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--smoke", action="store_true")
    parser.add_argument("--world-size", type=int, default=2)
    args = parser.parse_args()
    world = 2 if args.smoke else args.world_size

    job = create_spmd_job(
        job_name="spmd-example",
        world_size=world,
        env={"JAX_PLATFORMS": "cpu"},
    ).start()
    try:
        def rank_info(ctx):
            return {"rank": ctx.rank, "world": ctx.world_size}

        infos = job.run(rank_info)
        print("ranks:", sorted(i["rank"] for i in infos))
        assert sorted(i["rank"] for i in infos) == list(range(world))

        def gang_sum(ctx):
            # Every rank contributes rank+1; a real cross-process gloo
            # allreduce rendezvoused on the gang's coordinator address
            # (the pattern the Torch compat estimator uses for DDP).
            import torch
            import torch.distributed as dist

            host, port = ctx.coordinator_address.rsplit(":", 1)
            dist.init_process_group(
                "gloo",
                init_method=f"tcp://{host}:{int(port) + 1}",
                rank=ctx.rank,
                world_size=ctx.world_size,
            )
            try:
                t = torch.tensor([float(ctx.rank + 1)])
                dist.all_reduce(t)
                return float(t.item())
            finally:
                dist.destroy_process_group()

        sums = job.run(gang_sum)
        expected = world * (world + 1) // 2
        print("allreduce sums:", sums)
        assert all(s == expected for s in sums)
        print("spmd_job OK")
    finally:
        job.stop()


if __name__ == "__main__":
    main()
