"""Look at a profiler trace by hand, and cut a small fixture out of one.

    python3 perfbench/tools/trace_dump.py TRACE.xplane.pb[.gz]
        planes, their lines, event counts, and the first events of each
        line with their stats: what to read before writing a reader

    python3 perfbench/tools/trace_dump.py TRACE.xplane.pb[.gz] --fixture OUT.txt \
            [--steps 2] [--chips 0,1]
        an XSpace text proto holding only what perfbench/trace_reduce.py
        reads (the ``XLA Ops`` and ``XLA Modules`` lines of the chosen chips
        over the first ``--steps`` executions of the step program, the
        collectives of ``Async XLA Ops``, and the ``bench/`` annotations of
        the same stretch), re-based to time 0, every instruction cut down to
        its name, opcode, fusion kind and custom-call target.
        ``jax.profiler.ProfileData.from_text_proto`` reads it back.
"""

from __future__ import annotations

import argparse
import gzip
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from jax.profiler import ProfileData  # noqa: E402

from perfbench import trace_reduce  # noqa: E402


def read(path: str) -> ProfileData:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        return ProfileData.from_serialized_xspace(f.read())


def summary(data: ProfileData, n_events: int) -> None:
    for plane in data.planes:
        print(f"PLANE {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            print(f"  LINE {line.name!r}: {len(events)} events")
            for ev in events[:n_events]:
                stats = {k: (v if len(str(v)) < 80 else str(v)[:77] + "...")
                         for k, v in ev.stats}
                print(f"    {ev.name!r} start_ns={ev.start_ns:.0f} "
                      f"duration_ns={ev.duration_ns:.0f} {stats}")


def _quote(text: str) -> str:
    return '"' + text.replace("\\", "\\\\").replace('"', '\\"') + '"'


def compact(op: trace_reduce.Op) -> str:
    """The shortest instruction text that ``trace_reduce.parse_op`` reads
    back into the same name, opcode and label."""
    if op.label == op.opcode == op.name or "/" in op.name:
        return op.name                      # a module or a host annotation
    text = f"%{op.name} = x[] {op.opcode}()"
    detail = op.label[len(op.opcode) + 1:]
    if op.opcode == "fusion" and detail:
        text += f", kind={detail}"
    elif op.opcode == "custom-call" and detail:
        text += f', custom_call_target="{detail}"'
    return text


def fixture(data: ProfileData, steps: int, chips: set[int] | None) -> str:
    trace = trace_reduce.reduce_planes(data.planes)
    if trace is None:
        raise SystemExit("no /device:TPU plane in this trace")
    chosen = {c: chip for c, chip in trace.chips.items()
              if chips is None or c in chips}
    lo = min(chip.steps()[0][0] for chip in chosen.values())
    hi = max(chip.steps()[:steps][-1][1] for chip in chosen.values())
    out = []

    def plane(plane_id: int, name: str, lines: dict[str, list]) -> None:
        names: dict[str, int] = {}
        out.append(f"planes {{\n  id: {plane_id}\n  name: {_quote(name)}")
        for line_id, (line_name, ops) in enumerate(lines.items(), 1):
            out.append(f"  lines {{\n    id: {line_id}\n    name: "
                       f"{_quote(line_name)}\n    timestamp_ns: 0")
            for op in ops:
                meta = names.setdefault(compact(op), len(names) + 1)
                out.append(
                    f"    events {{ metadata_id: {meta} offset_ps: "
                    f"{round((op.start - lo) * 1000)} duration_ps: "
                    f"{round((op.end - op.start) * 1000)} }}")
            out.append("  }")
        for op_name, meta in names.items():
            out.append(f"  event_metadata {{ key: {meta} value {{ id: {meta} "
                       f"name: {_quote(op_name)} }} }}")
        out.append("}")

    def inside(ops):
        return [o for o in ops if o.start >= lo and o.end <= hi]

    for plane_id, (c, chip) in enumerate(sorted(chosen.items()), 1):
        plane(plane_id, f"/device:TPU:{c}",
              {trace_reduce.OPS_LINE: inside(chip.ops),
               trace_reduce.ASYNC_LINE: inside(
                   [o for o in chip.async_ops if trace_reduce.is_collective(o)]),
               trace_reduce.MODULES_LINE: inside(chip.modules)})
    plane(len(chosen) + 1, trace_reduce.HOST_PLANE,
          {"main": [o for o in trace.host if lo <= o.start < hi]})
    return "\n".join(out) + "\n"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace")
    ap.add_argument("--events", type=int, default=5)
    ap.add_argument("--fixture", metavar="OUT")
    ap.add_argument("--steps", type=int, default=2)
    ap.add_argument("--chips", default=None,
                    help="comma-separated chip numbers (default: all)")
    args = ap.parse_args(argv)
    data = read(args.trace)
    if args.fixture:
        chips = ({int(c) for c in args.chips.split(",")}
                 if args.chips else None)
        text = fixture(data, args.steps, chips)
        opener = gzip.open if args.fixture.endswith(".gz") else open
        with opener(args.fixture, "wt", encoding="utf-8") as f:
            f.write(text)
        print(f"{args.fixture}: {len(text)} characters")
    else:
        summary(data, args.events)
    return 0


if __name__ == "__main__":
    sys.exit(main())
