"""raydp_tpu — a TPU-native distributed ETL + training framework.

One Python program runs distributed Arrow-native data processing and
JAX/pjit model training on one cluster of TPU-VM hosts. Capability parity
with RayDP (reference mounted at /root/reference) with a TPU-first design:

  * ``raydp_tpu.init()`` / ``raydp_tpu.stop()`` — cluster lifecycle
    (reference: raydp.init_spark/stop_spark, python/raydp/context.py:154-217)
  * ``raydp_tpu.dataframe`` — partitioned Arrow DataFrame engine (the
    reference embeds Spark; we ship our own bounded-scope engine)
  * ``raydp_tpu.data.MLDataset`` — locality-aware sharded datasets feeding
    per-chip device_put infeed
  * ``raydp_tpu.train.JAXEstimator`` — scikit-learn-style distributed
    training; gradient sync is ``lax.psum`` over ICI, not NCCL
  * ``raydp_tpu.parallel`` — dp/pp/sp/tp device meshes, ring attention
  * ``raydp_tpu.spmd`` — SPMD host-process job runner (reference: MPI-on-Ray)
"""
import time as _time

#: ``perf_counter`` at the first import of this package, taken before the
#: imports below (jax alone is seconds): the origin of the process's
#: start-up record (``utils/profiling.mark_ready``).
IMPORTED_AT = _time.perf_counter()

from raydp_tpu.version import __version__  # noqa: E402

from raydp_tpu.context import connect, init, stop  # noqa: E402

__all__ = ["__version__", "connect", "init", "stop"]
