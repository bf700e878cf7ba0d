"""bagua-lint gates: AST rule fixtures, suppressions, the shrink-only
baseline, and the jaxpr collective-consistency checker (seeded divergences +
overlap-vs-serialized equivalence on the real step builders)."""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

import bagua_tpu
from bagua_tpu.analysis import Finding, run_ast_rules
from bagua_tpu.analysis.ast_rules import analyze_source
from bagua_tpu.analysis.findings import (
    load_baseline,
    save_baseline,
    split_by_baseline,
)
from bagua_tpu.analysis.jaxpr_check import (
    check_axis_binding,
    check_equivalence,
    collect,
    make_family_tracer,
    multiset,
)
from jax import shard_map

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.dirname(os.path.abspath(bagua_tpu.__file__))


def rules_of(source, path="fixture.py"):
    return [f.rule for f in analyze_source(path, textwrap.dedent(source))]


# ---- AST rule fixtures (positive + negative per rule) ---------------------


def test_host_sync_in_trace_positive():
    found = rules_of("""
        import jax
        import numpy as np

        def step(p, b):
            def per_shard(p, b):
                g = np.asarray(b)
                jax.device_get(p)
                v = float(g.sum())
                b.block_until_ready()
                return p
            return jax.jit(per_shard)(p, b)
    """)
    assert found.count("host-sync-in-trace") == 4


def test_host_sync_outside_trace_negative():
    # the same calls on the host side are fine
    found = rules_of("""
        import jax
        import numpy as np

        def host_eval(p, b):
            g = np.asarray(b)
            jax.device_get(p)
            return float(g.sum())
    """)
    assert "host-sync-in-trace" not in found


def test_host_sync_jnp_negative():
    found = rules_of("""
        import jax
        import jax.numpy as jnp

        def step(p):
            def traced(p):
                return jnp.asarray(p)[None]
            return jax.jit(traced)(p)
    """)
    assert "host-sync-in-trace" not in found


def test_raw_env_read_positive():
    found = rules_of("""
        import os
        a = os.environ.get("BAGUA_FIXTURE_X", "1")
        b = os.environ["BAGUA_FIXTURE_Y"]
        c = os.getenv("BAGUA_FIXTURE_Z")
    """)
    assert found.count("raw-env-read") == 3


def test_raw_env_read_negative():
    found = rules_of("""
        import os
        a = os.environ.get("HOME")
        b = os.environ.get("XLA_FLAGS", "")
    """)
    assert "raw-env-read" not in found


def test_raw_env_read_env_py_exempt():
    found = [
        f.rule
        for f in analyze_source(
            "bagua_tpu/env.py",
            'import os\nv = os.environ.get("BAGUA_ANYTHING")\n',
        )
    ]
    assert "raw-env-read" not in found


def test_tracer_leak_positive_and_negative():
    found = rules_of("""
        import jax

        class T:
            def go(self):
                def traced(x):
                    self.cache = x
                    return x * 2
                return jax.jit(traced)

            def host(self, x):
                self.cache = x  # host-side stash is fine
                return x
    """)
    assert found.count("tracer-leak") == 1


def test_py_rng_in_trace_positive_and_negative():
    found = rules_of("""
        import jax
        import random
        import numpy as np

        def step(p):
            def traced(p):
                a = random.random()
                b = np.random.randn(3)
                key = jax.random.PRNGKey(0)  # jax.random is fine
                return p + a + b.sum()
            return jax.jit(traced)(p)

        seed = random.random()  # host-side RNG is fine
    """)
    assert found.count("py-rng-in-trace") == 2


def test_dup_lambda_positive():
    found = rules_of("""
        import jax
        import jax.numpy as jnp
        f1 = lambda t: jax.tree.map(lambda x: jnp.asarray(x)[None], t)
        f2 = lambda t: jax.tree.map(lambda x: jnp.asarray(x)[None], t)
        f3 = lambda u: jax.tree.map(lambda x: jnp.asarray(x)[None], u)
    """)
    # arg-name normalization makes f3 a duplicate too; inner lambdas are
    # not double-reported
    assert found.count("dup-lambda") == 3


def test_dup_lambda_negative_two_copies_and_trivial():
    found = rules_of("""
        import jax
        import jax.numpy as jnp
        f1 = lambda t: jax.tree.map(lambda x: jnp.asarray(x)[None], t)
        f2 = lambda t: jax.tree.map(lambda x: jnp.asarray(x)[None], t)
        k1 = lambda p: p
        k2 = lambda p: p
        k3 = lambda p: p
    """)
    assert "dup-lambda" not in found


def test_torch_import_positive():
    found = rules_of("""
        import torch
        from torch.utils.data import DataLoader
    """)
    assert found.count("torch-import") == 2


def test_per_step_reflatten_positive_transform_fn():
    # the PRE-FIX contrib/fused_optimizer.update_fn pattern: per-dtype
    # concat of tree leaves inside an optax GradientTransformation (which
    # traces inside the jitted step by construction)
    found = rules_of("""
        import jax
        import jax.numpy as jnp
        import optax

        def fuse(inner):
            def update_fn(updates, state, params=None):
                leaves = jax.tree_util.tree_leaves(updates)
                flat = jnp.concatenate([jnp.ravel(l) for l in leaves])
                out, st = inner.update(flat, state, None)
                return out, st
            return optax.GradientTransformation(inner.init, update_fn)
    """)
    assert found.count("per-step-reflatten") == 1


def test_per_step_reflatten_positive_traced_step():
    found = rules_of("""
        import jax
        import jax.numpy as jnp

        def step(params, batch):
            leaves = jax.tree_util.tree_leaves(params)
            flat = jnp.concatenate([jnp.ravel(l) for l in leaves])
            return flat.sum()

        fn = jax.jit(step)
    """)
    assert found.count("per-step-reflatten") == 1


def test_per_step_reflatten_negative():
    # flatten without concat, concat without flatten, and an untraced
    # standalone helper are all idiom, not per-step repacking; the
    # flat-RESIDENT step consumes pre-flattened buffers and never flattens
    found = rules_of("""
        import jax
        import jax.numpy as jnp

        def helper(tree):
            return jnp.concatenate(
                [jnp.ravel(l) for l in jax.tree_util.tree_leaves(tree)]
            )

        def resident_step(flats, batch):
            return sum(f.sum() for f in flats)

        def flatten_only(params, batch):
            return sum(l.sum() for l in jax.tree_util.tree_leaves(params))

        f1 = jax.jit(resident_step)
        f2 = jax.jit(flatten_only)
    """)
    assert "per-step-reflatten" not in found


def test_per_step_reflatten_repo_is_clean():
    """The resident path (and the fixed fused optimizer) must lint clean."""
    findings = run_ast_rules([PKG], rel_to=REPO)
    assert not [f for f in findings if f.rule == "per-step-reflatten"], [
        (f.path, f.line) for f in findings if f.rule == "per-step-reflatten"
    ]


def test_unregistered_counter_positive_misspelled():
    # the canonical typo: a counter name one character off from a
    # registered one silently forks an unread metric
    found = rules_of("""
        from bagua_tpu.telemetry import counters

        def on_abort():
            counters.incr("comm/abortss")
            counters.set_gauge("async/staleness_maximum", 3)
    """)
    assert found.count("unregistered-counter") == 2


def test_unregistered_counter_incr_many_and_fstring():
    # literal dict keys in incr_many are checked too; f-string names pass
    # when SOME registered name fits the template, fail when none does
    found = rules_of("""
        from bagua_tpu.telemetry import counters

        def on_fire(point):
            counters.incr_many({"obs/flight_dumps": 1,
                                "obs/flite_dumps": 1})
            counters.incr(f"faults/{point}/fired")
            counters.incr(f"faults/{point}/exploded")
    """)
    assert found.count("unregistered-counter") == 2


def test_unregistered_counter_negative():
    # registered literals, matching f-string templates, and statically
    # unresolvable names (a variable) are all clean
    found = rules_of("""
        from bagua_tpu.telemetry import counters

        def ok(name):
            counters.incr("comm/aborts")
            counters.set_gauge("async/staleness_max", 2)
            counters.incr_many({"grad_guard/skipped_steps": 1})
            counters.incr(f"faults/{name}/recovered")
            counters.incr(name)
    """)
    assert "unregistered-counter" not in found


def test_unregistered_counter_repo_is_clean():
    """Every counter write site in the package names a registered metric."""
    findings = run_ast_rules([PKG], rel_to=REPO)
    assert not [f for f in findings if f.rule == "unregistered-counter"], [
        (f.path, f.line) for f in findings if f.rule == "unregistered-counter"
    ]


# ---- suppressions ---------------------------------------------------------


def test_suppression_trailing_and_standalone():
    src = """
        import os
        a = os.environ.get("BAGUA_FIXTURE_A")  # bagua: lint-ignore[raw-env-read] -- fixture
        # bagua: lint-ignore[raw-env-read] -- covers the next line
        b = os.environ.get("BAGUA_FIXTURE_B")
        c = os.environ.get("BAGUA_FIXTURE_C")
    """
    found = rules_of(src)
    assert found.count("raw-env-read") == 1  # only c survives


def test_suppression_wrong_rule_does_not_apply():
    found = rules_of("""
        import os
        a = os.environ.get("BAGUA_FIXTURE_A")  # bagua: lint-ignore[tracer-leak] -- wrong id
    """)
    assert "raw-env-read" in found


def test_suppression_without_reason_is_reported():
    found = rules_of("""
        import os
        a = os.environ.get("BAGUA_FIXTURE_A")  # bagua: lint-ignore[raw-env-read]
    """)
    assert "bad-suppression" in found
    assert "raw-env-read" in found  # the malformed suppression doesn't apply


# ---- baseline -------------------------------------------------------------


def test_baseline_round_trip_and_shrink_only(tmp_path):
    f1 = Finding("raw-env-read", "a.py", 3, "m", text="x = 1")
    f2 = Finding("raw-env-read", "b.py", 9, "m", text="y = 2")
    path = str(tmp_path / "baseline.json")
    save_baseline(path, [f1, f2])
    baseline = load_baseline(path)

    # same findings -> fully baselined, nothing new, nothing stale
    new, old, stale = split_by_baseline([f1, f2], baseline)
    assert not new and len(old) == 2 and not stale

    # line drift does not churn the baseline (fingerprint is rule+path+text)
    drifted = Finding("raw-env-read", "a.py", 30, "m", text="x = 1")
    new, old, stale = split_by_baseline([drifted, f2], baseline)
    assert not new and not stale

    # a fixed violation leaves a STALE entry (shrink-only: must prune)
    new, old, stale = split_by_baseline([f1], baseline)
    assert not new and len(stale) == 1

    # a new violation is NOT absorbed by the baseline
    f3 = Finding("tracer-leak", "c.py", 1, "m", text="self.x = t")
    new, old, stale = split_by_baseline([f1, f2, f3], baseline)
    assert new == [f3]


# ---- the repo itself is clean --------------------------------------------


def test_package_has_no_unsuppressed_findings():
    findings = run_ast_rules([PKG], rel_to=REPO)
    assert findings == [], "\n".join(f.render() for f in findings)


def test_cli_exits_zero_on_package():
    out = subprocess.run(
        [sys.executable, "-m", "bagua_tpu.analysis", "bagua_tpu/",
         "--no-jaxpr"],
        capture_output=True, text=True, cwd=REPO, timeout=300,
    )
    assert out.returncode == 0, out.stdout + out.stderr


def test_cli_flags_violations_and_baseline_flow(tmp_path):
    bad = tmp_path / "bad_module.py"
    bad.write_text(
        'import os\nv = os.environ.get("BAGUA_FIXTURE_CLI")\n'
    )
    base = [sys.executable, "-m", "bagua_tpu.analysis", str(bad), "--no-jaxpr"]
    env = {**os.environ, "PYTHONPATH": REPO}
    out = subprocess.run(base, capture_output=True, text=True, timeout=120,
                         cwd=str(tmp_path), env=env)
    assert out.returncode == 1 and "raw-env-read" in out.stdout

    # write a baseline, rerun: clean
    bl = str(tmp_path / "bl.json")
    subprocess.run(base + ["--write-baseline", "--baseline", bl],
                   capture_output=True, text=True, timeout=120,
                   cwd=str(tmp_path), env=env, check=True)
    out = subprocess.run(base + ["--baseline", bl], capture_output=True,
                         text=True, timeout=120, cwd=str(tmp_path), env=env)
    assert out.returncode == 0, out.stdout

    # fix the violation: the stale baseline entry now FAILS (shrink-only)
    bad.write_text("v = 1\n")
    out = subprocess.run(base + ["--baseline", bl], capture_output=True,
                         text=True, timeout=120, cwd=str(tmp_path), env=env)
    assert out.returncode == 1 and "STALE" in out.stdout


# ---- jaxpr checker --------------------------------------------------------


def _trace_shard_map(fn, n_args=1):
    mesh = Mesh(np.array(jax.devices()), ("dp",))
    specs = (P("dp"),) * n_args
    g = shard_map(fn, mesh=mesh, in_specs=specs, out_specs=P("dp"),
                  check_vma=False)
    args = [jnp.ones((8, 4)) for _ in range(n_args)]
    jitted = jax.jit(g)
    if hasattr(jitted, "trace"):
        return jitted.trace(*args).jaxpr
    return jax.make_jaxpr(g)(*args)


def test_jaxpr_flags_mismatched_cond_collectives():
    def bad(x):
        return jax.lax.cond(
            x.sum() > 0,
            lambda v: jax.lax.psum(v, "dp"),
            lambda v: v * 2.0,
            x,
        )

    _, findings = collect(_trace_shard_map(bad))
    assert [f.rule for f in findings] == ["cond-collective-divergence"]


def test_jaxpr_accepts_matched_cond_collectives():
    def good(x):
        return jax.lax.cond(
            x.sum() > 0,
            lambda v: jax.lax.psum(v, "dp"),
            lambda v: jax.lax.psum(v * 2.0, "dp"),
            x,
        )

    seq, findings = collect(_trace_shard_map(good))
    assert findings == []
    # the shared branch collective is counted once, not per branch
    assert [c.prim for c in seq] == ["psum"]


def test_jaxpr_axis_binding():
    def f(x):
        return jax.lax.psum(x, "dp")

    seq, _ = collect(_trace_shard_map(f))
    assert check_axis_binding(seq, ("dp",)) == []
    bad = check_axis_binding(seq, ("inter", "intra"))
    assert [b.rule for b in bad] == ["unbound-mesh-axis"]


@pytest.mark.parametrize("family", ["gradient_allreduce", "zero", "bytegrad"])
@pytest.mark.parametrize("accum", [1, 4])
def test_overlap_vs_serialized_collective_equivalence(family, accum):
    """PR 2's 'paths cannot drift' claim as a checked invariant, on the REAL
    step builders."""
    findings, report = check_equivalence(
        family, accum, make_family_tracer(family, accum)
    )
    assert findings == [], "\n".join(f.render() for f in findings)
    assert report["equal"]
    # byte accounting covered every bucket with at least one collective
    for row in report["serialized"]["buckets"]:
        assert row["collectives"], row


def test_equivalence_catches_seeded_divergence():
    """A construction with one extra collective must be flagged."""
    tracer = make_family_tracer("gradient_allreduce", 1)
    trainer, jaxpr_off = tracer("off")

    def extra(x):
        return jax.lax.psum(jax.lax.psum(x, "dp"), "dp")

    divergent = _trace_shard_map(extra)

    def fake_tracer(mode):
        return trainer, (jaxpr_off if mode == "off" else divergent)

    findings, report = check_equivalence("gradient_allreduce", 1, fake_tracer)
    assert not report["equal"]
    assert "overlap-serialized-divergence" in [f.rule for f in findings]


def test_multiset_ignores_order_but_not_shape():
    a = _trace_shard_map(lambda x: jax.lax.psum(x, "dp"))
    b = _trace_shard_map(lambda x: jax.lax.psum(x * 2.0, "dp"))
    sa, _ = collect(a)
    sb, _ = collect(b)
    assert multiset(sa) == multiset(sb)  # same signature, different compute
    c = _trace_shard_map(lambda x: jax.lax.psum(x[:, :2], "dp"))
    sc, _ = collect(c)
    assert multiset(sa) != multiset(sc)  # shape is part of the signature


# ---- concurrency engine (bagua-lint v2) -----------------------------------


from bagua_tpu.analysis.concurrency import (  # noqa: E402
    build_program,
    run_concurrency_rules,
    static_lock_graph,
)
from bagua_tpu.analysis.trace_coherence import run_trace_coherence  # noqa: E402
from bagua_tpu.analysis import lockdep as lockdep_mod  # noqa: E402
from bagua_tpu.analysis.suppressions import KNOWN_RULE_IDS  # noqa: E402

import threading  # noqa: E402


def _fx(**files):
    """name -> dedented source; underscores in kwargs become path slashes."""
    return {k.replace("__", "/") + ".py": textwrap.dedent(v)
            for k, v in files.items()}


def conc_rules(sources):
    return [f.rule for f in run_concurrency_rules(sources=sources)]


def test_lock_order_inversion_positive():
    rules = conc_rules(_fx(fx__mod="""
        import threading
        A = threading.Lock()
        B = threading.Lock()

        def forward():
            with A:
                with B:
                    pass

        def backward():
            with B:
                with A:
                    pass

        def start():
            threading.Thread(target=backward).start()
    """))
    assert "lock-order-inversion" in rules


def test_lock_order_consistent_negative():
    rules = conc_rules(_fx(fx__mod="""
        import threading
        A = threading.Lock()
        B = threading.Lock()

        def forward():
            with A:
                with B:
                    pass

        def also_forward():
            with A:
                with B:
                    pass

        def start():
            threading.Thread(target=also_forward).start()
    """))
    assert "lock-order-inversion" not in rules


def test_unguarded_shared_write_positive():
    """The pre-fix obs/spans.py shape: init under the lock, the test
    override without it."""
    rules = conc_rules(_fx(fx__mod="""
        import threading
        _STATE = None
        _LOCK = threading.Lock()

        def init():
            global _STATE
            with _LOCK:
                _STATE = 1

        def override(v):
            global _STATE
            _STATE = v

        def bg():
            init()

        def start():
            threading.Thread(target=bg).start()
    """))
    assert "unguarded-shared-write" in rules


def test_unguarded_shared_write_common_lock_negative():
    rules = conc_rules(_fx(fx__mod="""
        import threading
        _STATE = None
        _LOCK = threading.Lock()

        def init():
            global _STATE
            with _LOCK:
                _STATE = 1

        def override(v):
            global _STATE
            with _LOCK:
                _STATE = v

        def bg():
            init()

        def start():
            threading.Thread(target=bg).start()
    """))
    assert "unguarded-shared-write" not in rules


def test_unguarded_shared_write_single_root_negative():
    """No second thread root: a module global mutated only from the main
    context is not a race."""
    rules = conc_rules(_fx(fx__mod="""
        _STATE = None

        def init():
            global _STATE
            _STATE = 1

        def override(v):
            global _STATE
            _STATE = v
    """))
    assert "unguarded-shared-write" not in rules


def test_lock_held_io_positive_and_negative():
    src = """
        import threading
        import time
        _L = threading.Lock()

        def slow():
            with _L:
                time.sleep(1.0)

        def fast():
            with _L:
                x = 1
                return x

        def start():
            threading.Thread(target=slow).start()
    """
    assert "lock-held-io" in conc_rules(_fx(fx__mod=src))
    # single-root: nobody contends, the IO hurts nobody
    single = src.replace("threading.Thread(target=slow).start()", "slow()")
    assert "lock-held-io" not in conc_rules(_fx(fx__mod=single))


def test_signal_unsafe_lock_positive_pre_fix_sigterm_dump():
    """The pre-fix flight-record shape: the SIGTERM handler called the
    dump path directly, acquiring the dump lock from handler context."""
    rules = conc_rules(_fx(fx__rec="""
        import signal
        import threading
        _DUMP_LOCK = threading.Lock()

        def dump_flight_record():
            with _DUMP_LOCK:
                pass

        def _on_term(signum, frame):
            dump_flight_record()

        def install():
            signal.signal(signal.SIGTERM, _on_term)
    """))
    assert "signal-unsafe-lock" in rules


def test_signal_flag_defer_negative_post_fix_shape():
    """The post-fix shape: the handler only sets a flag; the dump runs
    from a normal context later."""
    rules = conc_rules(_fx(fx__rec="""
        import signal
        import threading
        _DUMP_LOCK = threading.Lock()
        _PENDING = threading.Event()

        def dump_flight_record():
            with _DUMP_LOCK:
                pass

        def _on_term(signum, frame):
            _PENDING.set()

        def install():
            signal.signal(signal.SIGTERM, _on_term)

        def maybe_dump():
            if _PENDING.is_set():
                dump_flight_record()
    """))
    assert "signal-unsafe-lock" not in rules


def test_non_reentrant_reacquire_positive_and_rlock_negative():
    src = """
        import threading
        _L = threading.Lock()

        def outer():
            with _L:
                inner()

        def inner():
            with _L:
                pass
    """
    assert "non-reentrant-reacquire" in conc_rules(_fx(fx__mod=src))
    rlock = src.replace("threading.Lock()", "threading.RLock()")
    assert "non-reentrant-reacquire" not in conc_rules(_fx(fx__mod=rlock))


def test_concurrency_suppression_applies():
    rules = conc_rules(_fx(fx__mod="""
        import threading
        _STATE = None
        _LOCK = threading.Lock()

        def init():
            global _STATE
            with _LOCK:
                _STATE = 1  # bagua: lint-ignore[unguarded-shared-write] -- fixture

        def override(v):
            global _STATE
            _STATE = v

        def bg():
            init()

        def start():
            threading.Thread(target=bg).start()
    """))
    assert "unguarded-shared-write" not in rules


def test_package_is_concurrency_and_trace_clean():
    """The committed package has zero findings from both v2 engines (the
    baseline stays empty) — and the model is NOT vacuous: it sees the
    package's locks, thread roots, and the codec env read."""
    p = build_program([PKG], rel_to=REPO)
    conc = run_concurrency_rules(program=p)
    assert conc == [], "\n".join(f.render() for f in conc)
    trace = run_trace_coherence(program=p)
    assert trace == [], "\n".join(f.render() for f in trace)
    assert len(p.locks) >= 10
    assert len(p.thread_roots) >= 5
    g = static_lock_graph(p)
    assert "bagua_tpu/obs/spans.py::_ENABLED_LOCK" in set(g["locks"].values())
    # the trace prover actually followed construction into the codec
    from bagua_tpu.analysis import trace_coherence as tc
    closure = tc._construction_closure(
        p, "bagua_tpu/core/backend.py::BaguaTrainer._make_step_fn")
    assert ("bagua_tpu/compression/codecs.py::TopKCodec.__init__"
            in closure)


def test_spans_set_enabled_holds_the_lock():
    """Regression for the unguarded-shared-write finding on obs/spans:
    the test override must take the same lock as the double-checked
    init."""
    from bagua_tpu.obs import spans

    class Probe:
        def __init__(self):
            self.entered = 0
            self._l = threading.Lock()

        def __enter__(self):
            self.entered += 1
            return self._l.__enter__()

        def __exit__(self, *exc):
            return self._l.__exit__(*exc)

    probe = Probe()
    orig_lock, orig_state = spans._ENABLED_LOCK, spans._ENABLED
    try:
        spans._ENABLED_LOCK = probe
        spans.set_enabled(True)
        assert probe.entered == 1
        assert spans.enabled() is True
    finally:
        spans._ENABLED_LOCK = orig_lock
        spans._ENABLED = orig_state


# ---- trace-coherence engine -----------------------------------------------


_TRACE_ENV_FX = """
    import os

    def _raw(name, default):
        return os.environ.get(name, default)

    def get_ratio():
        return float(_raw("BAGUA_FX_RATIO", "0.01"))
"""

_TRACE_PRE_FIX = """
    from .env import get_ratio

    class Codec:
        def __init__(self):
            self.ratio = get_ratio()

    CODECS = {"topk": Codec()}

    def get_codec(name):
        return CODECS[name]

    class Trainer:
        def __init__(self):
            self.plan = "p"

        def _step_key(self):
            return (self.plan,)

        def _make_step_fn(self):
            return get_codec("topk")
"""


def trace_rules(sources):
    return [f.rule for f in run_trace_coherence(sources=sources)]


def test_trace_flags_import_time_env_freeze_pre_fix_shape():
    """The PR 17 BAGUA_TOPK_RATIO bug: the codec singleton reads the env
    var at import, the key never carries it — a flip reuses a stale
    compiled step."""
    found = trace_rules(_fx(fx__env=_TRACE_ENV_FX,
                            fx__trainer=_TRACE_PRE_FIX))
    assert "trace-knob-not-keyed" in found


def test_trace_accepts_keyed_knob_post_fix_shape():
    keyed = _TRACE_PRE_FIX.replace(
        "return (self.plan,)", "return (self.plan, get_ratio())")
    found = trace_rules(_fx(fx__env=_TRACE_ENV_FX, fx__trainer=keyed))
    assert found == []


def test_trace_invariant_annotation_suppresses():
    annotated = _TRACE_PRE_FIX.replace(
        'return get_codec("topk")',
        'return get_codec("topk")  '
        '# bagua: trace-invariant[BAGUA_FX_RATIO] -- fixture: host-side only',
    )
    found = trace_rules(_fx(fx__env=_TRACE_ENV_FX, fx__trainer=annotated))
    assert found == []


def test_malformed_trace_invariant_is_reported():
    found = trace_rules(_fx(fx__mod="""
        # bagua: trace-invariant[get_ratio]
        X = 1
    """))
    assert "bad-trace-invariant" in found


def test_trace_flags_autotune_mutable_attr_not_keyed():
    src = """
        class Trainer:
            def __init__(self):
                self.overlap = "on"

            def _apply_recommendation(self, rec):
                self.overlap = rec

            def _step_key(self):
                return (1,)

            def _make_step_fn(self):
                return self.overlap
    """
    assert "trace-knob-not-keyed" in trace_rules(_fx(fx__trainer=src))
    keyed = src.replace("return (1,)", "return (self.overlap,)")
    assert trace_rules(_fx(fx__trainer=keyed)) == []


def test_trace_flags_transitive_autotune_knob_not_keyed():
    """Autotune-v2 shape (the ``_flat_resident`` knob): the mutation sits
    in a HELPER the recommendation path calls, not in
    ``_apply_recommendation`` itself — the prover must chase the
    transitive call closure, flag the unkeyed knob, and accept it once
    it rides the step key."""
    src = """
        class Trainer:
            def __init__(self):
                self._flat_resident = False

            def _apply_flat_resident(self, want):
                self._flat_resident = want == "on"

            def _apply_recommendation(self, rec):
                if rec.flat_resident:
                    self._apply_flat_resident(rec.flat_resident)

            def _step_key(self):
                return (1,)

            def _make_step_fn(self):
                return self._flat_resident
    """
    assert "trace-knob-not-keyed" in trace_rules(_fx(fx__trainer=src))
    keyed = src.replace("return (1,)", "return (1, self._flat_resident)")
    assert trace_rules(_fx(fx__trainer=keyed)) == []


def test_constructor_frozen_attr_is_exempt():
    """An attr set only in __init__ and read by construction needs no key
    entry: the per-instance step cache cannot go stale on it."""
    found = trace_rules(_fx(fx__trainer="""
        class Trainer:
            def __init__(self, donate):
                self.donate = donate

            def _apply_recommendation(self, rec):
                pass

            def _step_key(self):
                return (1,)

            def _make_step_fn(self):
                return self.donate
    """))
    assert found == []


# ---- suppression rule-id validation ----------------------------------------


def test_unknown_rule_id_suppression_is_reported():
    found = rules_of("""
        import os
        a = os.environ.get("BAGUA_FIXTURE_A")  # bagua: lint-ignore[no-such-rule] -- typo
    """)
    assert "bad-suppression" in found
    assert "raw-env-read" in found  # the typo'd suppression covers nothing


def test_known_rule_ids_match_engine_catalogs():
    from bagua_tpu.analysis.ast_rules import RULES as AST_RULES
    from bagua_tpu.analysis.concurrency import CONCURRENCY_RULES
    from bagua_tpu.analysis.lockdep import LOCKDEP_RULES
    from bagua_tpu.analysis.trace_coherence import TRACE_RULES

    ids = {r.id for r in (list(AST_RULES) + list(CONCURRENCY_RULES)
                          + list(TRACE_RULES) + list(LOCKDEP_RULES))}
    ids |= {"cond-collective-divergence", "unbound-mesh-axis",
            "overlap-serialized-divergence", "bad-suppression", "*"}
    assert ids == set(KNOWN_RULE_IDS)


# ---- lockdep runtime witness -----------------------------------------------


def test_lockdep_state_records_edges_and_inversions(tmp_path):
    st = lockdep_mod._LockdepState(
        pkg_dir="/nonexistent", out_path=str(tmp_path / "w.json"))
    a, b = ("m.py", 1), ("m.py", 2)
    la = lockdep_mod._InstrumentedLock(threading.Lock(), a, st)
    lb = lockdep_mod._InstrumentedLock(threading.Lock(), b, st)
    with la:
        with lb:
            pass
    w = st.witness()
    assert {"from": list(a), "to": list(b), "count": 1} in w["edges"]
    assert w["inversions"] == []
    with lb:
        with la:
            pass
    w = st.witness()
    assert len(w["inversions"]) == 1
    st.dump()
    assert lockdep_mod.load_witness(str(tmp_path / "w.json"))["inversions"]


def test_lockdep_reentrant_reacquire_is_not_an_edge(tmp_path):
    st = lockdep_mod._LockdepState(
        pkg_dir="/nonexistent", out_path=str(tmp_path / "w.json"))
    a = ("m.py", 1)
    la = lockdep_mod._InstrumentedLock(threading.RLock(), a, st)
    with la:
        with la:
            pass
    w = st.witness()
    assert w["edges"] == [] and w["inversions"] == []


def test_lockdep_cross_check():
    graph = {
        "locks": {("m.py", 1): "m.py::A", ("m.py", 2): "m.py::B"},
        "edges": {("m.py::A", "m.py::B"): "m.py:10"},
    }
    clean = {"edges": [{"from": ["m.py", 1], "to": ["m.py", 2],
                        "count": 3}], "inversions": []}
    assert lockdep_mod.cross_check(clean, graph) == []

    inverted = {"edges": [], "inversions": [
        {"a": ["m.py", 1], "b": ["m.py", 2], "thread": "t"}]}
    assert [f.rule for f in lockdep_mod.cross_check(inverted, graph)] == \
        ["lockdep-runtime-inversion"]

    unmodeled = {"edges": [{"from": ["m.py", 2], "to": ["m.py", 1],
                            "count": 1}], "inversions": []}
    assert [f.rule for f in lockdep_mod.cross_check(unmodeled, graph)] == \
        ["lockdep-unmodeled-edge"]

    # locks the static model does not catalog are not a gate
    foreign = {"edges": [{"from": ["x.py", 9], "to": ["m.py", 1],
                          "count": 1}], "inversions": []}
    assert lockdep_mod.cross_check(foreign, graph) == []


def test_lockdep_not_installed_by_default():
    assert lockdep_mod.maybe_install() is (lockdep_mod._STATE is not None)
    # BAGUA_LOCKDEP defaults off, and nothing in the test suite turns it
    # on for this process
    assert lockdep_mod._STATE is None


def test_cli_witness_gates_runtime_inversion(tmp_path):
    import json

    wit = tmp_path / "wit.json"
    wit.write_text(json.dumps({
        "edges": [],
        "inversions": [{"a": ["bagua_tpu/telemetry.py", 63],
                        "b": ["bagua_tpu/obs/spans.py", 47],
                        "thread": "t"}],
    }))
    out = subprocess.run(
        [sys.executable, "-m", "bagua_tpu.analysis", "bagua_tpu/",
         "--engine", "concurrency", "--witness", str(wit)],
        capture_output=True, text=True, cwd=REPO, timeout=300,
    )
    assert out.returncode == 1
    assert "lockdep-runtime-inversion" in out.stdout


def test_cli_engine_selection_runs_v2_clean():
    out = subprocess.run(
        [sys.executable, "-m", "bagua_tpu.analysis", "bagua_tpu/",
         "--engine", "concurrency,trace"],
        capture_output=True, text=True, cwd=REPO, timeout=300,
    )
    assert out.returncode == 0, out.stdout + out.stderr
