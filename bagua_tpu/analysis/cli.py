"""``bagua-lint`` CLI: ``python -m bagua_tpu.analysis [paths...]``.

Runs the selected engines (``--engine ast,jaxpr,concurrency,trace`` —
default all) over the given paths (default: the installed ``bagua_tpu``
package), compares against the shrink-only baseline, and exits non-zero on
any unsuppressed, unbaselined finding — the CI gate wired into
``scripts/ci.sh``:

* ``ast`` — per-module hot-path hygiene rules;
* ``jaxpr`` — the collective-consistency sweep over the algorithm families;
* ``concurrency`` — the whole-program host-concurrency race detector
  (lock-order inversions, unguarded shared writes, lock-held IO, …);
* ``trace`` — the step-cache-key coherence prover.

``--witness FILE`` additionally cross-checks a runtime lockdep witness
(produced by a ``BAGUA_LOCKDEP=on`` run) against the static acquisition
graph: zero runtime inversions and no witnessed edge the static model
misses.

The jaxpr sweep needs a device mesh; the CLI forces the same 8-way virtual
CPU mesh the test harness uses (``xla_force_host_platform_device_count``),
so results are deterministic on any machine, TPU or not.
"""

from __future__ import annotations

import argparse
import os
import sys
from typing import List, Optional

from .ast_rules import RULES, run_ast_rules
from .findings import (
    BASELINE_DEFAULT,
    Finding,
    load_baseline,
    save_baseline,
    split_by_baseline,
)


def _ensure_cpu_sim() -> None:
    """Pin the 8-device cpu-sim mesh BEFORE any jax backend initializes
    (same mechanism as tests/conftest.py and the launcher's dryrun)."""
    os.environ.setdefault("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in os.environ["XLA_FLAGS"]:
        os.environ["XLA_FLAGS"] += " --xla_force_host_platform_device_count=8"
    os.environ["JAX_PLATFORMS"] = "cpu"


def _default_paths() -> List[str]:
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return [pkg]


_ENGINES = ("ast", "jaxpr", "concurrency", "trace")


def _parse_engines(spec: str) -> List[str]:
    names = [e.strip() for e in spec.split(",") if e.strip()]
    if "all" in names:
        return list(_ENGINES)
    bad = [e for e in names if e not in _ENGINES]
    if bad:
        raise SystemExit(
            f"bagua-lint: unknown engine(s) {', '.join(bad)} "
            f"(choose from {', '.join(_ENGINES)}, or 'all')"
        )
    return names


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        "python -m bagua_tpu.analysis",
        description="bagua-lint: jaxpr collective-consistency checker + "
                    "AST hot-path analyzer",
    )
    ap.add_argument("paths", nargs="*", default=None,
                    help="files/directories to lint (default: bagua_tpu/)")
    ap.add_argument("--baseline", default=None,
                    help=f"baseline file (default: ./{BASELINE_DEFAULT} "
                         "when present)")
    ap.add_argument("--write-baseline", action="store_true",
                    help="rewrite the baseline with the current findings "
                         "(shrink-only workflow: run after fixing entries)")
    ap.add_argument("--engine", default="all",
                    help="comma-separated engines to run: "
                         f"{','.join(_ENGINES)} or 'all' (default)")
    ap.add_argument("--no-jaxpr", action="store_true",
                    help="skip the jaxpr consistency sweep (alias for "
                         "removing 'jaxpr' from --engine)")
    ap.add_argument("--jaxpr-only", action="store_true",
                    help="run only the jaxpr consistency sweep (alias for "
                         "--engine jaxpr)")
    ap.add_argument("--witness", default=None, metavar="FILE",
                    help="runtime lockdep witness JSON (from a "
                         "BAGUA_LOCKDEP=on run) to cross-check against "
                         "the static lock graph")
    ap.add_argument("--families", default=None,
                    help="comma-separated algorithm families for the jaxpr "
                         "sweep; a ':hier' suffix traces the hierarchical "
                         "two-level construction on a 2-slice mesh "
                         "(default: gradient_allreduce,zero,bytegrad plus "
                         "their :hier variants)")
    ap.add_argument("--accum-steps", default=None,
                    help="comma-separated accum_steps for the sweep "
                         "(default: 1,4)")
    ap.add_argument("--list-rules", action="store_true",
                    help="print the rule catalog and exit")
    ap.add_argument("-q", "--quiet", action="store_true",
                    help="findings only, no per-trace progress")
    args = ap.parse_args(argv)

    if args.list_rules:
        from .concurrency import CONCURRENCY_RULES
        from .lockdep import LOCKDEP_RULES
        from .trace_coherence import TRACE_RULES

        for title, rules in (
            ("ast", RULES),
            ("concurrency", CONCURRENCY_RULES),
            ("trace", TRACE_RULES),
            ("lockdep witness", LOCKDEP_RULES),
        ):
            print(f"-- {title} --")
            for r in rules:
                print(f"{r.id}: {r.summary}")
                print(f"    why:  {r.rationale}")
                print(f"    hint: {r.hint}")
        print("-- jaxpr --")
        print("cond-collective-divergence: cond/switch branches issue "
              "different collective sequences (jaxpr checker)")
        print("unbound-mesh-axis: collective axis not bound on the declared "
              "mesh (jaxpr checker)")
        print("overlap-serialized-divergence: overlap and serialized step "
              "constructions emit different collective multisets "
              "(jaxpr checker)")
        return 0

    engines = _parse_engines(args.engine)
    if args.jaxpr_only:
        engines = ["jaxpr"]
    if args.no_jaxpr:
        engines = [e for e in engines if e != "jaxpr"]

    findings: List[Finding] = []
    paths = args.paths or _default_paths()

    if "ast" in engines:
        findings.extend(run_ast_rules(paths))

    program = None
    if "concurrency" in engines or "trace" in engines or args.witness:
        from .concurrency import build_program

        program = build_program(paths)

    if "concurrency" in engines:
        from .concurrency import run_concurrency_rules

        findings.extend(run_concurrency_rules(program=program))

    if "trace" in engines:
        from .trace_coherence import run_trace_coherence

        findings.extend(run_trace_coherence(program=program))

    if args.witness:
        from .concurrency import static_lock_graph
        from .lockdep import cross_check, load_witness

        findings.extend(
            cross_check(load_witness(args.witness),
                        static_lock_graph(program))
        )

    if "jaxpr" in engines:
        _ensure_cpu_sim()
        from .jaxpr_check import (
            DEFAULT_ACCUM_STEPS,
            DEFAULT_FAMILIES,
            run_jaxpr_checks,
        )

        families = (
            tuple(f for f in args.families.split(",") if f)
            if args.families else DEFAULT_FAMILIES
        )
        accum = (
            tuple(int(a) for a in args.accum_steps.split(",") if a)
            if args.accum_steps else DEFAULT_ACCUM_STEPS
        )
        jaxpr_findings, reports = run_jaxpr_checks(families, accum)
        findings.extend(jaxpr_findings)
        if not args.quiet:
            for rep in reports:
                status = "OK " if rep.get("equal") else "FAIL"
                ser = rep["serialized"]["total_wire_bytes"]
                ovl = rep["overlap"]["total_wire_bytes"]
                n = len(rep["serialized"]["collectives"])
                print(
                    f"jaxpr[{status}] {rep['family']} "
                    f"accum={rep['accum_steps']}: {n} collectives, "
                    f"wire bytes serialized={ser} overlap={ovl}"
                )
                for row in rep["serialized"]["buckets"]:
                    print(
                        f"    bucket {row['bucket']}: flat "
                        f"{row['flat_bytes']} B -> {row['wire_bytes']} B on "
                        f"the wire across {len(row['collectives'])} "
                        "collectives"
                    )

    baseline_path = args.baseline
    if baseline_path is None and os.path.exists(BASELINE_DEFAULT):
        baseline_path = BASELINE_DEFAULT

    if args.write_baseline:
        out = baseline_path or BASELINE_DEFAULT
        save_baseline(out, findings)
        print(f"wrote {len(findings)} baseline entries to {out}")
        return 0

    stale: List = []
    baselined: List[Finding] = []
    if baseline_path:
        new, baselined, stale = split_by_baseline(
            findings, load_baseline(baseline_path)
        )
    else:
        new = findings

    for f in new:
        print(f.render())

    print(
        f"bagua-lint: {len(new)} finding(s)"
        + (f", {len(baselined)} baselined" if baselined else "")
        + (f", {len(stale)} STALE baseline entr(y/ies)" if stale else "")
    )
    if stale:
        for k in stale:
            print(f"  stale baseline entry (violation fixed — prune it): {k}")
        print(f"  shrink the baseline: python -m bagua_tpu.analysis "
              f"--write-baseline --baseline {baseline_path}")
    return 1 if (new or stale) else 0
