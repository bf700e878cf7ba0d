#!/usr/bin/env python
"""Generate the markdown API reference from docstrings (autoapi-style).

The reference ships a Sphinx docs site (/root/reference/docs/); this image
has no sphinx, so the generator is stdlib ``inspect`` emitting one markdown
file per module into ``docs/api/``.  Deterministic output — a test
regenerates and diffs, so the committed reference can't go stale.

Usage: python scripts/gen_api_docs.py [--check]
"""

from __future__ import annotations

import argparse
import importlib
import inspect
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
OUT = os.path.join(REPO, "docs", "api")

# documented module surface (import order = TOC order)
MODULES = [
    "bagua_tpu",
    "bagua_tpu.core.backend",
    "bagua_tpu.communication",
    "bagua_tpu.algorithms.base",
    "bagua_tpu.algorithms.gradient_allreduce",
    "bagua_tpu.algorithms.bytegrad",
    "bagua_tpu.algorithms.q_adam",
    "bagua_tpu.algorithms.decentralized",
    "bagua_tpu.algorithms.async_model_average",
    "bagua_tpu.algorithms.zero",
    "bagua_tpu.bucket",
    "bagua_tpu.tensor",
    "bagua_tpu.checkpoint",
    "bagua_tpu.watchdog",
    "bagua_tpu.faults.inject",
    "bagua_tpu.env",
    "bagua_tpu.compile_cache",
    "bagua_tpu.telemetry",
    "bagua_tpu.obs.spans",
    "bagua_tpu.obs.recorder",
    "bagua_tpu.obs.export",
    "bagua_tpu.obs.timeline",
    "bagua_tpu.obs.anomaly",
    "bagua_tpu.obs.ledger",
    "bagua_tpu.obs.memory",
    "bagua_tpu.obs.historian",
    "bagua_tpu.obs.http",
    "bagua_tpu.obs.step_observer",
    "bagua_tpu.obs.pauses",
    "bagua_tpu.autopilot.policy",
    "bagua_tpu.autopilot.engine",
    "bagua_tpu.podsim.util",
    "bagua_tpu.podsim.shaping",
    "bagua_tpu.podsim.collectives",
    "bagua_tpu.podsim.transport",
    "bagua_tpu.podsim.orchestrator",
    "bagua_tpu.profiling",
    "bagua_tpu.parallel.mesh",
    "bagua_tpu.parallel.tensor_parallel",
    "bagua_tpu.parallel.pipeline",
    "bagua_tpu.parallel.ring_attention",
    "bagua_tpu.parallel.ulysses",
    "bagua_tpu.model_parallel.moe.layer",
    "bagua_tpu.model_parallel.moe.gating",
    "bagua_tpu.models.mlp",
    "bagua_tpu.models.resnet",
    "bagua_tpu.models.vgg",
    "bagua_tpu.models.transformer",
    "bagua_tpu.models.linear_attention",
    "bagua_tpu.models.state_space",
    "bagua_tpu.models.generate",
    "bagua_tpu.serve",
    "bagua_tpu.serve.cache",
    "bagua_tpu.serve.engine",
    "bagua_tpu.serve.loader",
    "bagua_tpu.ops.flash_attention",
    "bagua_tpu.ops.gmm",
    "bagua_tpu.ops.embed_grad",
    "bagua_tpu.ops.rope",
    "bagua_tpu.ops.moe_rows",
    "bagua_tpu.ops.gated_delta",
    "bagua_tpu.ops.gated_delta_rows",
    "bagua_tpu.ops.ssd",
    "bagua_tpu.ops.ssd_rows",
    "bagua_tpu.ops.tiles",
    "bagua_tpu.compression.codecs",
    "bagua_tpu.compression.minmax_uint8",
    "bagua_tpu.compression.pallas_codec",
    "bagua_tpu.contrib.fused_optimizer",
    "bagua_tpu.contrib.load_balancing_data_loader",
    "bagua_tpu.contrib.cache_loader",
    "bagua_tpu.contrib.cached_dataset",
    "bagua_tpu.contrib.sync_batchnorm",
    "bagua_tpu.contrib.digits_data",
    "bagua_tpu.contrib.utils.store",
    "bagua_tpu.contrib.utils.tcp_store",
    "bagua_tpu.contrib.utils.redis_store",
    "bagua_tpu.service.autotune_service",
    "bagua_tpu.service.autotune_task_manager",
    "bagua_tpu.service.bayesian_optimizer",
    "bagua_tpu.distributed.run",
    "bagua_tpu.elastic.membership",
    "bagua_tpu.elastic.coordinator",
    "bagua_tpu.elastic.failover",
    "bagua_tpu.elastic.resize",
    "bagua_tpu.script.baguarun",
    "bagua_tpu.analysis",
    "bagua_tpu.analysis.ast_rules",
    "bagua_tpu.analysis.jaxpr_check",
    "bagua_tpu.analysis.findings",
    "bagua_tpu.analysis.suppressions",
    "bagua_tpu.analysis.concurrency",
    "bagua_tpu.analysis.trace_coherence",
    "bagua_tpu.analysis.lockdep",
    "bagua_tpu.define",
    "bagua_tpu.utils",
]


import re

_ADDR = re.compile(r" at 0x[0-9a-fA-F]+")


def _sig(obj) -> str:
    try:
        # strip memory addresses (flax module defaults embed function reprs)
        return _ADDR.sub("", str(inspect.signature(obj)))
    except (ValueError, TypeError):
        return "(...)"


def _doc(obj) -> str:
    d = inspect.getdoc(obj)
    # flax dataclass auto-docstrings embed object reprs with addresses
    return _ADDR.sub("", (d or "").strip())


def _public_members(mod):
    names = getattr(mod, "__all__", None)
    if names is None:
        names = [n for n in vars(mod) if not n.startswith("_")]
    out = []
    for n in names:
        obj = getattr(mod, n, None)
        if obj is None or inspect.ismodule(obj):
            continue
        # only objects defined in (or re-exported by) this package
        owner = getattr(obj, "__module__", "") or ""
        if not owner.startswith("bagua_tpu") and mod.__name__ != "bagua_tpu":
            continue
        if inspect.isclass(obj) or inspect.isfunction(obj):
            out.append((n, obj))
    return out


def render_module(name: str) -> str:
    mod = importlib.import_module(name)
    lines = [f"# `{name}`", ""]
    if _doc(mod):
        lines += [_doc(mod), ""]
    for n, obj in _public_members(mod):
        if inspect.isclass(obj):
            lines += [f"## class `{n}{_sig(obj)}`", ""]
            if _doc(obj):
                lines += [_doc(obj), ""]
            for mn, meth in sorted(vars(obj).items()):
                if mn.startswith("_") or not callable(meth):
                    continue
                fn = meth.__func__ if isinstance(meth, (staticmethod, classmethod)) else meth
                if not (inspect.isfunction(fn) or inspect.ismethod(fn)):
                    continue
                lines += [f"### `{n}.{mn}{_sig(fn)}`", ""]
                if _doc(fn):
                    lines += [_doc(fn), ""]
        else:
            lines += [f"## `{n}{_sig(obj)}`", ""]
            if _doc(obj):
                lines += [_doc(obj), ""]
    return "\n".join(lines).rstrip() + "\n"


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--check", action="store_true",
                    help="verify committed docs match (exit 1 on drift)")
    args = ap.parse_args()

    os.makedirs(OUT, exist_ok=True)
    index = ["# API reference", "",
             "Generated by `scripts/gen_api_docs.py` — do not edit by hand.",
             ""]
    drift = []
    for name in MODULES:
        text = render_module(name)
        fname = name.replace(".", "_") + ".md"
        index.append(f"- [`{name}`]({fname})")
        path = os.path.join(OUT, fname)
        if args.check:
            old = open(path).read() if os.path.exists(path) else None
            if old != text:
                drift.append(name)
        else:
            with open(path, "w") as f:
                f.write(text)
    index_text = "\n".join(index) + "\n"
    index_path = os.path.join(OUT, "index.md")
    if args.check:
        old = open(index_path).read() if os.path.exists(index_path) else None
        if old != index_text:
            drift.append("<index>")
        if drift:
            print("API docs out of date for:", ", ".join(drift))
            print("regenerate with: python scripts/gen_api_docs.py")
            return 1
        print(f"API docs up to date ({len(MODULES)} modules)")
        return 0
    with open(index_path, "w") as f:
        f.write(index_text)
    print(f"wrote {len(MODULES)} module pages to {OUT}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
