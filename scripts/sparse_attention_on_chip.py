"""What only a chip can say of ``ops/sparse_attention.py``, in one call:

    chiprun --timeout 2400 -- python scripts/sparse_attention_on_chip.py \
        [--seed N] [--skip-model] [--out chiprun_out/sparse_on_chip.json]

1. THE MASK. The key at a query's threshold EQUALS it, so a backward whose
   index scores differ from the selection's in the last bit drops that key.
   On every sparse layer's own ``qI``, ``kI``, ``w`` (captured from the
   forward of ``keye_vl_2_0_30b_a3b.fit_s16384``'s model on the check's
   sequence, weights as the cell draws them) the mask as the backward
   kernel builds it (:func:`backward_mask`: ``_selected_turned`` in the
   backward's tile with the ReLUs kept) is compared pair by pair with the
   selection (``index_scores`` + ``select_threshold``) and with float32
   "highest" scores ranked by ``lax.top_k``.
2. BOTH BACKWARD PATHS at the cell's shapes (one sequence, 32 heads over 4
   of 128, 16 index heads of 64, 2,048 keys a query, bf16, random
   operands): the one kernel (16,384 tokens, where the rule on the call's
   shapes sends it), the ``dq`` and ``dk/dv`` pair forced at 16,384, and
   the pair at 32,768 tokens, where the rule sends the call by itself; all
   six gradients against the dense float32 formula taken a block of query
   rows at a time (:func:`reference_in_blocks`), and forward + backward by
   the host's clock.

The CPU tests run :func:`mask_agreement` and :func:`gradient_errors` at
their tiles in the interpreter (``tests/test_keye_sparse_attention.py``),
so the script cannot rot unseen; its numbers mean something on a TPU only.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.experimental import pallas as pl  # noqa: E402
from jax.experimental.pallas import tpu as pltpu  # noqa: E402

from raydp_tpu.ops import sparse_attention as sa  # noqa: E402

CELL = "keye_vl_2_0_30b_a3b.fit_s16384"
_F32 = jnp.float32


# ------------------------------------------------------------------ the mask

def backward_mask(q_idx, k_idx, w, tau, row0):
    """``keep`` [R, S] of the query rows ``row0 …`` as
    ``sparse_attention._backward_kernel`` makes it: ``q_idx`` [Hi, R, Di],
    ``k_idx`` [S, Di], ``w`` [R, Hi], ``tau`` [R]."""
    heads, rows, d = q_idx.shape
    s = k_idx.shape[0]
    tq, tk = sa._block(sa.BLOCK_Q, rows), sa._block(sa.BLOCK_KV, s)

    def kernel(row0_ref, qi_ref, ki_ref, w_ref, tau_ref, o_ref, relu_ref):
        q0 = row0_ref[0] + pl.program_id(0) * tq
        k0 = pl.program_id(1) * tk
        _, keep = sa._selected_turned(
            qi_ref, ki_ref[...], w_ref[...], tau_ref[...], q0, k0, relu_ref
        )
        o_ref[...] = keep.astype(jnp.int32)

    out = pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct((s, rows), jnp.int32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(rows // tq, s // tk),
            in_specs=[
                pl.BlockSpec((heads, tq, d), lambda i, j, r: (0, i, 0)),
                pl.BlockSpec((tk, d), lambda i, j, r: (j, 0)),
                pl.BlockSpec((tq, heads), lambda i, j, r: (i, 0)),
                pl.BlockSpec((1, tq), lambda i, j, r: (0, i)),
            ],
            out_specs=pl.BlockSpec((tk, tq), lambda i, j, r: (j, i)),
            scratch_shapes=[pltpu.VMEM((heads, tq, tk), _F32)],
        ),
        compiler_params=sa._params("parallel", "arbitrary"),
        interpret=sa._interpret(), name="backward_mask",
    )(jnp.asarray(row0, jnp.int32).reshape(1), q_idx, k_idx, w, tau[None, :])
    return out.T > 0


def plain_scores(q_idx, k_idx, w):
    """``I`` [R, S] as plain ``jax.numpy``: ``q_idx`` [R, Hi, Di]."""
    z = jnp.einsum("thd,sd->hts", q_idx, k_idx)
    return jnp.einsum("th,hts->ts", w, jnp.maximum(z, 0.0))


def mask_agreement(layers, topk: int, reference_scores=plain_scores,
                   rows: int = 512):
    """Per layer ``(qI [S, Hi, Di], kI [S, Di], w [S, Hi])``: the pairs the
    selection keeps, the pairs the backward's mask keeps, how many DIFFER
    (0 is the claim), and the share of a float32 "highest" ``lax.top_k``
    selection that the backward's mask holds too."""

    @jax.jit
    def block(qi, ki, w, r0):
        qi_t = jnp.swapaxes(qi, 0, 1)
        scores = sa.index_scores(qi_t, ki, w, r0)
        tau, _, count = sa.select_threshold(scores, topk)
        mine = jnp.logical_and(scores >= tau[:, None], scores > -jnp.inf)
        back = backward_mask(qi_t, ki, w, tau, r0)
        with jax.default_matmul_precision("highest"):
            ref = reference_scores(
                qi.astype(_F32), ki.astype(_F32), w.astype(_F32))
        causal = jnp.arange(ki.shape[0])[None, :] <= (
            r0 + jnp.arange(qi.shape[0])[:, None])
        ref = jnp.where(causal, ref, -jnp.inf)
        tau_ref = jax.lax.top_k(ref, min(topk, ref.shape[1]))[0][:, -1]
        theirs = jnp.logical_and(ref >= tau_ref[:, None], causal)
        return (mine.sum(), back.sum(), jnp.logical_xor(mine, back).sum(),
                theirs.sum(), jnp.logical_and(back, theirs).sum(),
                (count > topk).sum())

    out = {}
    for name, (qi, ki, w) in layers.items():
        total = np.zeros(6, np.int64)
        for r0 in range(0, qi.shape[0], rows):
            total += np.asarray([int(v) for v in block(
                qi[r0:r0 + rows], ki, w[r0:r0 + rows], r0)])
        out[name] = {
            "selection_pairs": int(total[0]),
            "backward_mask_pairs": int(total[1]),
            "pairs_that_differ": int(total[2]),
            "float32_top_k_pairs": int(total[3]),
            "backward_holds_of_float32": total[4] / total[3],
            "overfull_queries": int(total[5]),
        }
        print(name, out[name], flush=True)
    return out


def model_layers(seed: int):
    """``({layer: (qI, kI, w)}, the cell's own readings)`` of the cell's
    model after a 4 s fit through the benchmark's job: the check's sequence
    through the program's forward, every index branch's output captured."""
    sys.path[:0] = [os.path.join(ROOT, "benchmark")]
    import harness
    import run
    from flax.traverse_util import flatten_dict

    from raydp_tpu.models import stats

    cell = harness.load_cell(ROOT, CELL)
    out_dir = os.path.join(ROOT, "benchmark_out")
    os.makedirs(out_dir, exist_ok=True)
    ctx = run.Context(cell, seed, 4.0, 0, "tpu", out_dir, time.perf_counter())
    try:
        result = cell.part("jobs", "fit_window").run(ctx)
        est = result["estimator"]
        _, detail = harness.check_reference(cell, est, seed)
        x = jnp.asarray(cell.model.check_batch(cell.sizes, cell.traffic, seed))
        model = est._model
        _, mut = jax.jit(lambda p, x: model.apply(
            p, x, capture_intermediates=lambda m, _: m.name == "index",
            mutable=["intermediates", "losses", stats.STATS],
        ))(est._state.params, x)
        layers = {
            "/".join(path[:-1]): tuple(a[0] for a in value[0])
            for path, value in flatten_dict(dict(mut["intermediates"])).items()
            if path[-1] == "__call__"
        }
        return layers, cell.sizes["sa_config"]["topk"], {
            "train_samples_per_s_of_4s":
                result["end_to_end"]["train_samples_per_s"],
            "program_against_reference":
                detail["max_abs_err_over_max_abs_ref"],
        }, lambda qi, ki, w: cell.model._index_scores(qi, ki, w, None)
    finally:
        ctx.close()


# ------------------------------------------------------------ the gradients

def draw(seed: int, s: int, b: int = 1, h: int = 32, h_kv: int = 4,
         d: int = 128, h_i: int = 16, d_i: int = 64, dtype=jnp.bfloat16):
    """Operands and the two cotangents of one call."""
    keys = jax.random.split(jax.random.PRNGKey(seed % (2 ** 31)), 8)
    n = lambda k, *shape: jax.random.normal(k, shape, _F32)  # noqa: E731
    args = (
        n(keys[0], b, s, h, d).astype(dtype),
        n(keys[1], b, s, h_kv, d).astype(dtype),
        n(keys[2], b, s, h_kv, d).astype(dtype),
        n(keys[3], b, s, h_i, d_i).astype(dtype),
        n(keys[4], b, s, d_i).astype(dtype),
        n(keys[5], b, s, h_i) * (h_i * d_i) ** -0.5,
    )
    return args, (n(keys[6], b, s, h, d).astype(dtype), n(keys[7], b, s))


def reference_in_blocks(args, cot, topk: int, rows: int):
    """The six gradients of ``Σ o·cot_o + Σ kl·cot_kl`` by autodiff of the
    dense float32 formula (``reference_sparse_attention``'s, its own
    ranking by ``lax.top_k``), ``rows`` query rows at a time so that no
    [S, S] array exists; ``B`` = 1."""
    f = lambda a: a[0].astype(_F32)  # noqa: E731
    q, k, v, q_idx, k_idx, w = (f(a) for a in args)
    cot_o, cot_kl = (f(a) for a in cot)
    s, h, d = q.shape
    group, scale = h // k.shape[1], d ** -0.5

    @jax.checkpoint
    def block(q, k, v, q_idx, k_idx, w, r0):
        rows_of = lambda a: jax.lax.dynamic_slice_in_dim(  # noqa: E731
            a, r0, rows, 0)
        scores = plain_scores(rows_of(q_idx), k_idx, rows_of(w))
        causal = jnp.arange(s)[None, :] <= r0 + jnp.arange(rows)[:, None]
        ranked = jnp.where(causal, jax.lax.stop_gradient(scores), -jnp.inf)
        tau = jax.lax.top_k(ranked, min(topk, s))[0][:, -1:]
        keep = jnp.logical_and(causal, ranked >= tau)
        logits = jnp.einsum(
            "thd,shd->hts", rows_of(q), jnp.repeat(k, group, axis=1)) * scale
        probs = jax.nn.softmax(jnp.where(keep, logits, -jnp.inf), axis=-1)
        out = jnp.einsum("hts,shd->thd", probs, jnp.repeat(v, group, axis=1))
        mean = jax.lax.stop_gradient(probs.mean(axis=0))
        log_r = jax.nn.log_softmax(
            jnp.where(keep, scores, -jnp.inf), axis=-1)
        kl = jnp.where(
            mean > 0.0, mean * (jnp.log(jnp.maximum(mean, 1e-37)) - log_r),
            0.0,
        ).sum(axis=-1)
        return (out * rows_of(cot_o)).sum() + (kl * rows_of(cot_kl)).sum()

    def loss(*operands):
        return jax.lax.map(
            lambda r0: block(*operands, r0), jnp.arange(0, s, rows)).sum()

    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.grad(loss, range(6)))(q, k, v, q_idx, k_idx, w)


def kernel_gradients(args, cot, topk: int, pair: bool):
    """``(the six gradients, the backward's kernels by name, ms of forward +
    backward by the host's clock)`` of the operation, by the path the rule
    on the call's shapes takes or, where ``pair``, with the pair forced (a
    chip with no VMEM)."""
    def loss(*a):
        out, kl, _ = sa.sparse_attention(*a, topk)
        return (out.astype(_F32) * cot[0].astype(_F32)).sum() + (
            kl * cot[1]).sum()

    probe = sa.vmem_bytes
    if pair:
        sa.vmem_bytes = lambda: 0
    try:
        grads = jax.jit(jax.grad(loss, range(6)))
        text = str(jax.make_jaxpr(grads)(*args))
        names = sorted(
            n for n in ("sparse_attention_backward", "sparse_attention_dq",
                        "sparse_attention_dkv") if n in text)
        got = jax.block_until_ready(grads(*args))
    finally:
        sa.vmem_bytes = probe
    start = time.perf_counter()
    for _ in range(3):
        last = grads(*args)
    jax.block_until_ready(last)
    return got, names, (time.perf_counter() - start) / 3 * 1e3


def gradient_errors(seed: int, s: int, topk: int, rows: int,
                    paths=("rule", "pair"), **sizes):
    """Each path's gradients against :func:`reference_in_blocks`: the
    largest error of each of ``dq, dk, dv, dqI, dkI, dw`` over the
    reference's largest entry."""
    args, cot = draw(seed, s, **sizes)
    want = reference_in_blocks(args, cot, topk, rows)
    out = {}
    for path in paths:
        got, names, ms = kernel_gradients(args, cot, topk, path == "pair")
        out[path] = {"kernels": names, "forward_backward_ms": ms, "error": {
            name: float(jnp.max(jnp.abs(g[0].astype(_F32) - r))
                        / jnp.max(jnp.abs(r)))
            for name, g, r in zip(
                ("dq", "dk", "dv", "dqI", "dkI", "dw"), got, want)
        }}
        print(s, path, out[path], flush=True)
    return out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, default=2200000277)
    parser.add_argument("--skip-model", action="store_true")
    parser.add_argument("--out", default="chiprun_out/sparse_on_chip.json")
    opts = parser.parse_args(argv)
    if jax.default_backend() != "tpu":
        print("not on a TPU: nothing here is a chip's number", file=sys.stderr)
        return 3
    out = {"seed": opts.seed, "device": jax.devices()[0].device_kind,
           "constants": {n: getattr(sa, n) for n in (
               "BLOCK_Q", "BLOCK_KV", "HEAD_UNROLL")},
           "vmem_bytes": sa.vmem_bytes()}
    out["gradients"] = {
        "s16384": gradient_errors(opts.seed, 16384, 2048, 128),
        "s32768": gradient_errors(
            opts.seed + 1, 32768, 2048, 128, paths=("rule",)),
    }
    if not opts.skip_model:
        layers, topk, readings, scores = model_layers(opts.seed)
        out["cell"] = readings
        out["mask"] = mask_agreement(layers, topk, scores)
    os.makedirs(os.path.dirname(opts.out), exist_ok=True)
    with open(opts.out, "w") as fh:
        json.dump(out, fh, indent=1)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
