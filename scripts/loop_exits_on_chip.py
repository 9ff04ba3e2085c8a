"""A looped LM's cell on the chip, by hand (PR 61): what the benchmark's one
comparison does not read.

    chiprun --timeout 1800 -- python scripts/loop_exits_on_chip.py [--seed N] [--seconds S]

Runs ``ouro_2_6b.fit_s8192`` as ``benchmark/run.py`` does (``run_cell``, a
traced run), then on the state the run's training left and the check
batch of the seed, at the published widths:

1. the TIMED step's loss and the four exit shares (the program's training
   apply and ``loop_exit_crossentropy``, no gradient) against the
   builder's ``reference_loss`` (float32 "highest", forward only);
2. what ``harness.check_reference`` reads for the reference in the
   precision below the stated one and for each of the builder's
   departures (the two readings a tolerance lies between);
3. the traced window's device time by pass and by exit (a second
   reduction of the run's profile).

Writes ``chiprun_out/loop_exits_on_chip.json``. A TPU only.
"""
import argparse
import glob
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "benchmark"), ROOT]
CELL = "ouro_2_6b.fit_s8192"
BY_PASS = [
    [r"(^|/)part:(update|grad_norm)(/|$)", "update"],
    [r"/encoder/tok_embed(/|$)", "embed"],
    *[[rf"(^|/)exit_{t}(/|$)", f"exit_{t}"] for t in range(4)],
    *[[rf"/encoder/pass_{t}/", f"pass_{t}"] for t in range(4)],
]


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--seed", type=int, default=2200000061)
    parser.add_argument("--seconds", type=float, default=30.0)
    # A rehearsal off the chip: a tiny copy of the cell in a temporary tree.
    parser.add_argument("--root", default=ROOT)
    parser.add_argument("--cell", default=CELL)
    parser.add_argument("--platform", default="tpu")
    args = parser.parse_args()

    import harness
    import program_trace
    import run

    kept = {}
    check = harness.check_reference

    def keeping(cell, est, seed, flip=False):
        kept.update(cell=cell, est=est)
        return check(cell, est, seed, flip=flip)

    harness.check_reference = keeping
    out = run.run_cell(args.root, args.cell, args.seed, args.seconds, trace=1,
                       platform=args.platform)
    harness.check_reference = check
    result = {"line": out["line"], "seed": args.seed}

    import jax
    import jax.numpy as jnp
    import numpy as np

    from raydp_tpu.models import step as model_step
    from raydp_tpu.train.losses import loop_exit_crossentropy
    from raydp_tpu.utils.profiling import metrics

    cell, est = kept["cell"], kept["est"]
    sizes, model = cell.sizes, cell.model
    result["gauges"] = {
        name: value for name, value in metrics.snapshot()["gauges"].items()
        if name.split("/")[0] in ("loop", "checkpoint")
    }
    x = model.check_batch(sizes, cell.traffic, args.seed)
    params = est._state.params
    step_model = est._step_model or est._model

    # 1. the timed step's loss and exit shares against the reference.
    @jax.jit
    def program(v, ids):
        preds, sown = step_model.apply(
            v, ids, mutable=model_step.SOWN,
            **model_step.apply_kwargs(est._model, jax.random.PRNGKey(0)))
        stats = model_step.step_stats(sown)
        tokens = stats["loop_tokens"]
        return (loop_exit_crossentropy(preds, ids),
                stats["loop_exit_mass"] / tokens,
                stats["loop_exit_entropy"] / tokens)

    t0 = time.perf_counter()
    got = [np.asarray(a, np.float64).tolist() for a in program(params, x)]
    want = [np.asarray(a, np.float64).tolist() for a in jax.jit(
        lambda v, ids: model.reference_loss(v, ids, sizes))(params, x)]
    lower = [np.asarray(a, np.float64).tolist() for a in jax.jit(
        lambda v, ids: model.reference_loss(
            v, ids, sizes, trunk=jnp.bfloat16))(params, x)]
    result["loss_and_shares"] = {
        "program": got, "reference": want, "reference_bf16_trunk": lower,
        "seconds": time.perf_counter() - t0,
    }
    print("LOSS", json.dumps(result["loss_and_shares"]), flush=True)

    # 2. the comparison's two readings.
    plain = model.reference_logits
    readings = {"program": out["notes"]["reference_check"]}
    for name in ("trunk_bfloat16", *model.DEPARTURES):
        given = {"trunk": jnp.bfloat16} if name == "trunk_bfloat16" else {
            "depart": name}
        model.reference_logits = lambda p, b, s, given=given: plain(
            p, b, s, **given)
        t0 = time.perf_counter()
        # The reference under the change against the PLAIN reference
        # would say what the change is worth; the harness's comparison
        # (program against changed reference) says whether ``correct``
        # tells it apart, which is what the tolerance is for.
        ok, detail = check(cell, est, args.seed)
        readings[name] = dict(detail, correct=ok,
                              seconds=time.perf_counter() - t0)
        print("READING", name, json.dumps(readings[name]), flush=True)
    model.reference_logits = plain
    result["readings"] = readings

    # 3. the traced window by pass and by exit.
    paths = sorted(glob.glob(os.path.join(
        args.root, "benchmark_out", "trace", "plugins", "profile", "*",
        "*.xplane.pb")))
    if paths:
        summary, _ = program_trace.reduce_profile(
            program_trace.load_profile(paths[-1]), BY_PASS)
        result["by_pass_ms"] = summary.get("parts_ms", {})
        result["step_device_ms"] = summary.get("step_device_ms")
        print("BY_PASS", json.dumps(result["by_pass_ms"]), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(
            ROOT, "chiprun_out", "loop_exits_on_chip.json"), "w") as f:
        json.dump(result, f, indent=1, default=str)
    for name in (args.cell + ".program_trace.json",
                 args.cell + ".trace1.json", args.cell + ".startup.json"):
        src = os.path.join(args.root, "benchmark_out", name)
        if os.path.exists(src):
            with open(src) as f, open(os.path.join(
                    ROOT, "chiprun_out", "loop_" + name), "w") as g:
                g.write(f.read())
    print(json.dumps(out["line"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
