"""What the block checkpoint's rule (``models/step.fit_checkpoint``) counts
of a benchmark cell, and what the TPU's compiler makes of a choice: no chip.

    python scripts/checkpoint_rows.py <cell> [--chips 4]
        the cell's ``Stack`` (one abstract trace a kind of block, as the chip
        would make it), the rule's choice at a v5e's limit and the estimate
        with the LAST k blocks released, for every k.
    python scripts/checkpoint_rows.py <cell> --sets "4,5;3,4,5" [--dump DIR]
        also compiles the cell's train step for a DESCRIBED v5e with each of
        the sets released and prints ``memory_analysis()`` arguments +
        temporaries beside the estimate: a row of the calibration table in
        ``models/step.py`` and ``tests/test_checkpoint_fit.py`` (30-120 s a
        set). With ``--dump`` (an empty directory; ONE set) XLA writes its
        buffer assignment there and the values live where the temporaries
        peak are listed by block.

Program sizes, not device numbers. A four-chip cell needs ``--chips 4``.
"""
import argparse
import collections
import dataclasses
import glob
import importlib
import json
import os
import re
import sys
import time

V5E = 16_909_336_064   # memory_stats()["bytes_limit"] of one v5e chip
GIB, MIB = 2 ** 30, 2 ** 20


def arguments():
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("cell")
    parser.add_argument("--chips", type=int, default=1)
    parser.add_argument("--sets", default="", help="'4,5;3,4,5': released blocks, a compile each; 'none' for ()")
    parser.add_argument("--dump", default="")
    return parser.parse_args()


def peak(dump):
    """The temporaries' values live where their sum is greatest, from XLA's
    buffer assignment and the scheduled module: a value lives from its first
    position to its last use on the entry computation's schedule (one inside
    a conditional or a loop at its caller's place, so such a primitive's
    inside reads as all live at once: an upper reading)."""
    assignment = glob.glob(dump + "/*jit_train_step*after_optimizations-buffer-assignment.txt")[0]
    module = glob.glob(dump + "/*jit_train_step*after_optimizations_after_buffer_assignment.txt")[0]
    computation_of, order, callers, op_name = {}, [], {}, {}
    current = entry = None
    header = re.compile(r"^(ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$")
    instruction = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = ")
    called = re.compile(
        r"(?:calls|to_apply|body|condition|branch_computations|true_computation|false_computation)"
        r"=\{?%?([\w.\-, %]+)\}?")
    for line in open(module):
        if found := header.match(line):
            current = found.group(2)
            entry = current if found.group(1) else entry
        elif (found := instruction.match(line)) and current:
            name = found.group(1)
            computation_of[name] = current
            if current == entry:
                order.append(name)
            scope = re.search(r'op_name="([^"]*)"', line)
            op_name[name] = scope.group(1) if scope else ""
            for each in called.finditer(line):
                for callee in each.group(1).replace("%", "").split(","):
                    callers[callee.strip()] = name
    at = {name: i for i, name in enumerate(order)}

    def when(name):
        for _ in range(64):
            if name in at:
                return at[name]
            name = callers.get(computation_of.get(name))
        return None

    lines = open(assignment).read().split("\n")
    temporaries, i = {}, 0
    while i < len(lines):
        if lines[i].startswith("allocation ") and "preallocated-temp" in lines[i]:
            i += 1
            while i < len(lines) and lines[i].startswith(" value:"):
                found = re.match(r" value: <(\d+) (\S+)(?: \S+)? @\d+> \(size=(\d+),offset=\d+\): (\S+)", lines[i])
                if found:
                    temporaries[int(found.group(1))] = (found.group(2), int(found.group(3)), found.group(4))
                i += 1
        else:
            i += 1
    spans, value, reading = {}, None, False
    for line in lines[lines.index("Used values:") + 1:]:
        if found := re.match(r"^<(\d+) ", line):
            value, reading = int(found.group(1)), False
        elif line.startswith((" positions:", " uses:")):
            reading = True
        elif line.startswith(" from instruction"):
            reading = False
        elif reading and line.startswith("  "):
            t = when(line.strip().split(",")[0].split(" ")[0])
            if t is not None:
                first, last = spans.get(value, (t, t))
                spans[value] = (min(first, t), max(last, t))
    live = [(*spans[v], *temporaries[v]) for v in temporaries if v in spans]
    change = [0] * (len(order) + 2)
    for first, last, _, size, _ in live:
        change[first] += size
        change[last + 1] -= size
    most = now = where = 0
    for t, delta in enumerate(change):
        now += delta
        if now > most:
            most, where = now, t
    print(f"  temporaries read {most / MIB:.0f} MiB at {order[where]} ({op_name[order[where]][-90:]})")

    def scope(name):
        path = op_name.get(name.split("{")[0], "")
        block = re.search(r"(block_\d+|lm_head|tok_embed|part:\w+)", path)
        side = "backward" if "transpose(" in path else "forward"
        return f"{block.group(1) if block else 'other'} {side}"

    by_scope = collections.Counter()
    for first, last, name, size, _ in live:
        if first <= where <= last:
            by_scope[scope(name)] += size
    print("  by scope, MiB:", {k: round(v / MIB) for k, v in by_scope.most_common(14)})
    held = sorted((v for v in live if v[0] <= where <= v[1] and v[1] - v[0] > 0), key=lambda v: -v[3])
    for first, last, name, size, shape in held[:24]:
        print(f"  {size / MIB:8.1f} MiB [{first:5d}, {last:5d}] {shape[:40]:40s} {op_name.get(name.split('{')[0], '')[-80:]}")


def main():
    args = arguments()
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    flags = [f"--xla_force_host_platform_device_count={args.chips}"] if args.chips > 1 else []
    if args.dump:
        flags += [f"--xla_dump_to={args.dump}", "--xla_dump_hlo_as_text"]
    os.environ["XLA_FLAGS"] = " ".join(filter(None, [os.environ.get("XLA_FLAGS")] + flags))
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [root, os.path.join(root, "benchmark")]

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

    import harness
    from raydp_tpu.models import step as model_step
    from raydp_tpu.parallel import MeshSpec
    from raydp_tpu.train import JAXEstimator

    jax.config.update("jax_enable_compilation_cache", False)
    # The branches a TPU takes: the kernels, and the VMEM they are sized to.
    jax.default_backend = lambda: "tpu"
    flash = importlib.import_module("raydp_tpu.ops.flash_attention")
    flash.vmem_bytes = lambda: 128 * MIB
    importlib.import_module("raydp_tpu.ops.sparse_attention").vmem_bytes = flash.vmem_bytes

    sets = [
        () if each == "none" else tuple(int(i) for i in each.split(","))
        for each in args.sets.split(";") if each
    ]
    topology = None
    if sets:
        from jax.experimental import topologies
        topology = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        if args.chips > 1:
            build = MeshSpec.build
            MeshSpec.build = lambda self, devices=None: build(self, list(topology.devices))

    cell = harness.load_cell(root, args.cell)
    traffic = cell.traffic
    spec = MeshSpec(**traffic.get("mesh", {}))
    batch = traffic["per_chip_batch"] * spec.dp
    est = JAXEstimator(
        **cell.model.estimator_kwargs(cell.sizes, traffic, spec), batch_size=batch, mesh=spec, seed=1,
        epoch_mode=traffic["epoch_mode"])
    est._row_plan = False
    mesh = est._ensure_mesh()
    rng = jax.random.PRNGKey(1)
    sample = jnp.zeros((1, traffic["seq_len"]), jnp.int32)
    init, shardings = est._init_program(rng, sample)
    abstract = jax.eval_shape(init, rng, sample)
    if isinstance(shardings, jax.sharding.Sharding):
        shardings = jax.tree_util.tree_map(lambda _: shardings, abstract)
    one = SingleDeviceSharding(topology.devices[0]) if topology and args.chips == 1 else None
    state = jax.tree_util.tree_map(
        lambda leaf, sharding: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype, sharding=one or sharding),
        abstract, shardings)

    # Abstract leaves have no shards to read: one chip's bytes from the layout.
    model_step._chip_bytes = lambda tree: sum(
        int(np.prod(leaf.sharding.shard_shape(leaf.shape))) * np.dtype(leaf.dtype).itemsize
        for leaf in jax.tree_util.tree_leaves(tree))
    model_step.device_limit = lambda mesh: V5E
    seen = {}
    rule = model_step.released_blocks
    model_step.released_blocks = lambda stack, limit: seen.setdefault("stack", stack) and rule(stack, limit)
    est._state = state
    est._sample_batch = jax.ShapeDtypeStruct((batch, traffic["seq_len"]), jnp.int32)
    t0 = time.perf_counter()
    chosen = model_step.fit_checkpoint(est._model, state, est._sample_batch, mesh).cfg.released
    stack = seen["stack"]
    print(f"{args.cell}: the rule releases {chosen} ({time.perf_counter() - t0:.2f} s)")
    print("STACK", json.dumps({
        name: [round(v / MIB) for v in value] if isinstance(value, list) else round(value / MIB)
        for name, value in stack._asdict().items() if name not in ("passes", "exits")}),
        f"(MiB); {stack.passes} pass(es), {stack.exits} exit(s)")
    n = len(stack.released)
    for k in range(n + 1):
        estimate = model_step.estimated_bytes(stack, tuple(range(n - k, n)))
        print(f"  last {k} released: estimate {estimate.total / GIB:6.2f} GiB = {stack.fixed / GIB:.2f} + "
              f"{model_step.SLACK} x ({estimate.held >> 20} held + {estimate.working >> 20} working MiB)")

    for released in sets:
        est._step_model = est._model.clone(cfg=dataclasses.replace(est._model.cfg, released=released))
        over = NamedSharding(mesh, PartitionSpec("dp")) if args.chips > 1 else one
        whole = NamedSharding(mesh, PartitionSpec()) if args.chips > 1 else one
        x = jax.ShapeDtypeStruct((batch, traffic["seq_len"]), jnp.int32, sharding=over)
        key = jax.ShapeDtypeStruct(rng.shape, rng.dtype, sharding=whole)
        row = dict(cell=args.cell, released=list(released),
                   estimate_gib=round(model_step.estimated_bytes(stack, released).total / GIB, 3))
        t0 = time.perf_counter()
        try:
            memory = jax.jit(est._make_train_step(), donate_argnums=(0,)).lower(
                state, x, None, key).compile().memory_analysis()
            row["compiled_gib"] = round((memory.argument_size_in_bytes + memory.temp_size_in_bytes) / GIB, 3)
        except Exception as error:  # what the chip's compiler would raise
            row["error"] = str(error)[:400]
        row["seconds"] = round(time.perf_counter() - t0, 1)
        print("ROW", json.dumps(row), flush=True)
        if args.dump and "compiled_gib" in row:
            peak(args.dump)


if __name__ == "__main__":
    main()
