"""Are a looped LM's exits live one at a time in the COMPILED step? No chip.

    python scripts/checkpoint_rows.py ouro_2_6b.fit_s8192 --sets "4,5" --dump <empty dir>
    python scripts/loop_exits_live.py <that dir> [--mib 700]

Reads XLA's buffer assignment of the dumped ``jit_train_step`` (the step
compiled for a described v5e) and lists every value at least ``--mib``
large (an exit's logits ``f32[8192,49152]`` are 1,536 MiB, their gradient
as the head's backward products read it 768) with its live range on the
schedule, its offset in the temporaries and the scope that writes it; then
says whether values of two DIFFERENT exits (``exit_<t>`` in the scope) are
ever live at once, and the most bytes of such values live together.
Program sizes, not device numbers.
"""
import argparse
import glob
import re


def read(dump: str, least: int):
    """``[(name, bytes, type, (first, last), offset, scope)]`` of the
    dumped step's values of ``least`` bytes or more, by first position."""
    assignment = glob.glob(
        dump + "/*jit_train_step*after_optimizations-buffer-assignment.txt")[0]
    module = glob.glob(
        dump + "/*jit_train_step*after_optimizations_after_buffer_assignment"
        ".txt")[0]
    value = re.compile(
        r"^\s+value: <\d+ ([\w.\-]+ ?(?:\{[^}]*\})?) ?@\d+> "
        r"\(size=(\d+),offset=(\d+)\): (\S+)")
    live = re.compile(r"^\s+([\w.\-]+\{[^}]*\}):(\d+)-(\d+)$")
    sizes, ranges, in_ranges = {}, {}, False
    for line in open(assignment):
        if found := value.match(line):
            name = found.group(1).replace(" ", "").replace("{}", "")
            sizes[name] = (
                int(found.group(2)), found.group(4), int(found.group(3)))
        if line.strip() == "BufferLiveRange:":
            in_ranges = True
        elif in_ranges and (found := live.match(line)):
            ranges.setdefault(
                found.group(1).replace("{}", ""),
                (int(found.group(2)), int(found.group(3))))
        elif line.strip().startswith("Live ranges at"):
            in_ranges = False
    scope = {}
    for line in open(module):
        if found := re.match(
                r"^\s+(?:ROOT )?%?([\w.\-]+) = .*op_name=\"([^\"]+)\"", line):
            scope[found.group(1)] = found.group(2)
    rows = [
        (name, size, kind.split("{")[0], ranges[name], offset,
         scope.get(name.split("{")[0], ""))
        for name, (size, kind, offset) in sizes.items()
        if size >= least and name in ranges
    ]
    return sorted(rows, key=lambda row: row[3])


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("dump")
    parser.add_argument("--mib", type=int, default=700)
    args = parser.parse_args()
    rows = read(args.dump, args.mib * 2 ** 20)
    exit_of = {}
    for name, size, kind, (first, last), offset, scope in rows:
        exits = re.findall(r"exit_(\d+)", scope)
        exit_of[name] = exits[-1] if exits else None
        print(f"{size / 2 ** 20:8.1f} MiB [{first:6d}, {last:6d}] "
              f"@{offset:<11d} exit {exit_of[name] or '-'}  {kind:22} "
              f"{scope[-72:]}")
    together = [
        (a[0], b[0]) for i, a in enumerate(rows) for b in rows[i + 1:]
        if exit_of[a[0]] and exit_of[b[0]] and exit_of[a[0]] != exit_of[b[0]]
        and a[3][0] <= b[3][1] and b[3][0] <= a[3][1]
    ]
    print("values of two exits live at once:", together or "none")
    # A value is live up to AND AT its last position.
    live = most = 0
    for _, delta in sorted(
            [(row[3][0], row[1]) for row in rows if exit_of[row[0]]]
            + [(row[3][1] + 1, -row[1]) for row in rows if exit_of[row[0]]]):
        live += delta
        most = max(most, live)
    print(f"most bytes of the exits' values live at once: "
          f"{most / 2 ** 20:.0f} MiB")


if __name__ == "__main__":
    main()
