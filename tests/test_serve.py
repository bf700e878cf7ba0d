"""Serving plane: continuous-batching engine over the paged KV-cache.

The engine's golden invariant mirrors test_generate's: the paged pool and
the continuous-batching scheduler are OPTIMIZATIONS, not a semantics
change — greedy decode through the engine must be bit-identical to
``models.generate.generate()`` for every request, regardless of slot
placement, mid-batch joins, chunked prefill, page reuse after eviction,
or preemption-and-recompute under pool exhaustion."""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bagua_tpu.models.generate import generate
from bagua_tpu.models.transformer import TransformerConfig, TransformerLM
from bagua_tpu.serve import (
    PagePool,
    Request,
    ServeConfig,
    ServeEngine,
    ServeQueueFull,
    load_serving_params,
    save_serving_artifact,
)
from bagua_tpu.telemetry import counters

CFG = TransformerConfig(vocab_size=61, d_model=32, n_heads=4, n_layers=2,
                        d_ff=64, max_seq_len=32, dtype=jnp.float32)


@pytest.fixture(scope="module")
def model_and_params():
    model = TransformerLM(CFG)
    probe = jax.random.randint(jax.random.PRNGKey(0), (1, 5), 0, 61)
    params = model.init(jax.random.PRNGKey(1), probe)["params"]
    return model, params


def _cfg(**kw):
    base = dict(max_slots=3, page_size=4, num_pages=2 + 3 * 8,
                queue_depth=64, prefill_chunk=1, tick_idle_s=0.001)
    base.update(kw)
    return ServeConfig(**base)


def _ref(model, params, prompt, n):
    """The dense-cache greedy continuation (batch 1) — the golden model."""
    out = generate(model, params, jnp.asarray(np.asarray(prompt)[None]), n)
    return np.asarray(out)[0]


def _drain(engine, cap=5000):
    """Drive to empty; returns the sum of step()'s completed counts (must
    equal the requests the drain finished, chunk-path completions
    included)."""
    steps = 0
    done = 0
    while not engine.idle:
        done += engine.step()
        steps += 1
        assert steps < cap, "engine failed to drain"
    return done


def test_paged_decode_bit_identical_to_generate(model_and_params):
    """Different-length requests sharing the pool: every output sequence
    equals the dense generate() continuation exactly."""
    model, params = model_and_params
    eng = ServeEngine(model, params, _cfg())
    prompts = [np.array([1, 2, 3, 4, 5]), np.array([7, 8]),
               np.array([9, 10, 11])]
    budgets = [6, 8, 4]
    before_decode = counters.get("serve/decode_tokens")
    reqs = [eng.submit(p, n) for p, n in zip(prompts, budgets)]
    assert _drain(eng) == 3  # step()'s completed counts cover every path
    for req, prompt, n in zip(reqs, prompts, budgets):
        np.testing.assert_array_equal(
            np.asarray(req.output), _ref(model, params, prompt, n))
        assert req.ttft_s is not None and req.ttft_s >= 0
        assert req.t_done is not None
    # decode_tokens == total output tokens delivered (first tokens too)
    assert counters.get("serve/decode_tokens") - before_decode == \
        sum(budgets)


def test_mid_batch_join_and_evict_continuity(model_and_params):
    """A request admitted while another is mid-decode — and one admitted
    into a slot (and pages) an earlier eviction freed — both continue the
    exact greedy chain."""
    model, params = model_and_params
    eng = ServeEngine(model, params, _cfg(max_slots=2))
    rng = np.random.RandomState(3)
    pa = rng.randint(0, 61, size=6)
    pb = rng.randint(0, 61, size=3)
    pc = rng.randint(0, 61, size=4)
    ra = eng.submit(pa, 12)
    for _ in range(5):
        eng.step()  # ra is mid-flight
    rb = eng.submit(pb, 4)   # joins mid-batch
    while rb.t_done is None:
        eng.step()
    # rb finished and was evicted while ra still runs; rc reuses the slot
    assert ra.t_done is None
    rc = eng.submit(pc, 6)
    _drain(eng)
    for req, prompt, n in ((ra, pa, 12), (rb, pb, 4), (rc, pc, 6)):
        np.testing.assert_array_equal(
            np.asarray(req.output), _ref(model, params, prompt, n))


def test_chunked_prefill_bit_identical(model_and_params):
    """Prompts far longer than the chunk stream through the chunked
    prefill program; outputs stay bit-identical and the chunk counter
    moves."""
    model, params = model_and_params
    before = counters.get("serve/prefill_chunks")
    eng = ServeEngine(model, params, _cfg(max_slots=2, prefill_chunk=4))
    rng = np.random.RandomState(11)
    prompts = [rng.randint(0, 61, size=13), rng.randint(0, 61, size=9)]
    reqs = [eng.submit(p, 6) for p in prompts]
    _drain(eng)
    for req, prompt in zip(reqs, prompts):
        np.testing.assert_array_equal(
            np.asarray(req.output), _ref(model, params, prompt, 6))
    assert counters.get("serve/prefill_chunks") > before
    # a request whose chunk consumes the whole prompt AND whose budget is
    # one token completes on the chunk path — step() must report it
    short = rng.randint(0, 61, size=4)  # == prefill_chunk
    r1 = eng.submit(short, 1)
    assert _drain(eng) == 1
    np.testing.assert_array_equal(
        np.asarray(r1.output), _ref(model, params, short, 1))


def test_pool_exhaustion_backpressure(model_and_params):
    """A pool sized for ~1.5 requests under 8 mixed-length requests:
    everything queues/preempts-and-recomputes to completion — bit
    identical, never a crash."""
    model, params = model_and_params
    eng = ServeEngine(model, params,
                      _cfg(max_slots=4, num_pages=2 + 10, prefill_chunk=1))
    rng = np.random.RandomState(7)
    specs = [(rng.randint(0, 61, size=rng.randint(2, 12)),
              int(rng.randint(2, 14))) for _ in range(8)]
    reqs = [eng.submit(p, n) for p, n in specs]
    _drain(eng)
    for req, (prompt, n) in zip(reqs, specs):
        np.testing.assert_array_equal(
            np.asarray(req.output), _ref(model, params, prompt, n))
    assert counters.get("serve/pool_exhausted") >= 1
    assert counters.get("serve/requests_preempted") >= 1
    assert any(r.preemptions > 0 for r in reqs)


def test_queue_depth_backpressure(model_and_params):
    model, params = model_and_params
    eng = ServeEngine(model, params, _cfg(queue_depth=2))
    eng.submit([1, 2], 2)
    eng.submit([3, 4], 2)
    with pytest.raises(ServeQueueFull):
        eng.submit([5, 6], 2)
    assert counters.get("serve/requests_rejected") >= 1
    _drain(eng)


def test_submit_validation(model_and_params):
    model, params = model_and_params
    eng = ServeEngine(model, params, _cfg())
    with pytest.raises(ValueError, match="max_seq_len"):
        eng.submit(np.arange(10), CFG.max_seq_len)
    with pytest.raises(ValueError, match="at least one token"):
        eng.submit(np.array([], np.int32), 4)
    # generate(prompt, 0) returns an empty continuation; the engine
    # rejects rather than emitting one unrequested token
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.submit(np.arange(3), 0)


def test_static_batching_mode_holds_admissions(model_and_params):
    """The A/B baseline: a formed batch runs to FULL completion before the
    next admission (and still decodes bit-identically)."""
    model, params = model_and_params
    eng = ServeEngine(model, params, _cfg(max_slots=2), continuous=False)
    rng = np.random.RandomState(5)
    prompts = [rng.randint(0, 61, size=4) for _ in range(3)]
    reqs = [eng.submit(p, n) for p, n in zip(prompts, (2, 9, 3))]
    # drive until the first batch (r0 at 2 tokens, r1 at 9) fully drains:
    # r2 must NOT have been admitted while r1 was still running
    while reqs[1].t_done is None:
        eng.step()
        assert reqs[2].t_first_token is None
    _drain(eng)
    for req, prompt, n in zip(reqs, prompts, (2, 9, 3)):
        np.testing.assert_array_equal(
            np.asarray(req.output), _ref(model, params, prompt, n))


def test_serving_ledger_classes_fed(model_and_params):
    """The goodput ledger books the engine's walls under the serving
    classes, and goodput_fraction counts prefill+decode as goodput."""
    from bagua_tpu.obs import ledger as obs_ledger

    model, params = model_and_params
    obs_ledger.ledger.reset()
    try:
        eng = ServeEngine(model, params, _cfg(prefill_chunk=4))
        eng.submit(np.arange(9), 6)
        eng.submit(np.arange(3), 4)
        _drain(eng)
        rep = obs_ledger.ledger.report()
        assert rep["classes"]["prefill"] > 0, rep
        assert rep["classes"]["decode"] > 0, rep
        assert rep["goodput_fraction"] > 0.5, rep
        # serving goodput is not misread as badput
        assert "prefill" not in obs_ledger.BADPUT_CLASSES
        assert "decode" not in obs_ledger.BADPUT_CLASSES
        assert "batch_formation_idle" in obs_ledger.BADPUT_CLASSES
        assert "weight_load" in obs_ledger.BADPUT_CLASSES
    finally:
        obs_ledger.ledger.reset()


def test_run_defers_arrivals_at_queue_depth(model_and_params):
    """A burst beyond queue_depth must be DEFERRED by the run loop (the
    never-crash backpressure contract), not raise ServeQueueFull out of
    the replay."""
    model, params = model_and_params
    eng = ServeEngine(model, params, _cfg(max_slots=1, queue_depth=2))
    trace = [(0.0, np.array([i + 1, i + 2]), 3) for i in range(6)]
    done = eng.run(trace)
    assert len(done) == 6
    for req, (_, prompt, n) in zip(sorted(done, key=lambda r: r.rid),
                                   trace):
        np.testing.assert_array_equal(
            np.asarray(req.output), _ref(model, params, prompt, n))


def test_run_replays_timed_trace(model_and_params):
    model, params = model_and_params
    eng = ServeEngine(model, params, _cfg())
    trace = [(0.0, np.array([1, 2, 3]), 4), (0.01, np.array([4, 5]), 3),
             (0.05, np.array([6]), 2)]
    done = eng.run(trace)
    assert len(done) == 3
    for req, (_, prompt, n) in zip(sorted(done, key=lambda r: r.rid),
                                   trace):
        np.testing.assert_array_equal(
            np.asarray(req.output), _ref(model, params, prompt, n))


# ---- paged-cache unit behavior --------------------------------------------


def test_page_pool_alloc_free():
    pool = PagePool(6)  # 4 usable
    pages = [pool.alloc() for _ in range(4)]
    assert None not in pages and len(set(pages)) == 4
    assert all(p >= 2 for p in pages)  # reserved zero/trash never handed out
    assert pool.alloc() is None        # exhaustion returns None, no raise
    pool.free(pages[:2])
    assert pool.free_pages == 2
    with pytest.raises(AssertionError, match="double free"):
        pool.free(pages[:1] + pages[:1])


def test_engine_rejects_undersized_pool(model_and_params):
    model, params = model_and_params
    with pytest.raises(ValueError, match="full-length"):
        ServeEngine(model, params, _cfg(num_pages=4))


# ---- integrity-verified serving loads -------------------------------------


def test_serving_artifact_round_trip(model_and_params, tmp_path):
    """Flat serving artifact -> digest-verified load -> leaf params equal
    to the originals; the loaded params decode identically."""
    model, params = model_and_params
    d = str(tmp_path / "artifact")
    save_serving_artifact(d, params, step=3)
    step, loaded = load_serving_params(
        d, jax.eval_shape(lambda: params))
    assert step == 3
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(loaded)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert counters.get("serve/weight_loads") >= 1
    prompt = np.array([5, 6, 7])
    np.testing.assert_array_equal(
        _ref(model, loaded, prompt, 5), _ref(model, params, prompt, 5))


def test_serving_load_detects_corruption(model_and_params, tmp_path):
    """A flipped byte in the newest artifact fails the digest and the load
    falls back to the previous verified step (training's integrity-chain
    policy, now guarding the serving path)."""
    from bagua_tpu.checkpoint import CheckpointIntegrityError

    model, params = model_and_params
    mutated = jax.tree.map(lambda x: x + 1.0, params)
    d = str(tmp_path / "artifact")
    save_serving_artifact(d, params, step=1)
    save_serving_artifact(d, mutated, step=2)
    # the array data, not orbax's own small JSON metadata (the first file
    # over 256 bytes in glob order can be _CHECKPOINT_METADATA, which the
    # restore does not need: the content digest rightly still verifies)
    files = [f for f in glob.glob(os.path.join(d, "2", "**"),
                                  recursive=True) if os.path.isfile(f)]
    data = max(files, key=os.path.getsize)
    assert os.path.getsize(data) > 4096, "expected a data file to corrupt"
    with open(data, "r+b") as f:
        f.seek(128)
        f.write(b"\xff" * 64)
    before = counters.get("ckpt/fallback_restores")
    step, loaded = load_serving_params(d, jax.eval_shape(lambda: params))
    assert step == 1  # fell back to the older verified artifact
    assert counters.get("ckpt/fallback_restores") == before + 1
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(loaded)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # an explicit step never falls back — corruption raises
    with pytest.raises(CheckpointIntegrityError):
        load_serving_params(d, jax.eval_shape(lambda: params), step=2)


def test_serving_load_rejects_wrong_model(model_and_params, tmp_path):
    """An artifact for another model config is a configuration error, not
    a silent mis-load."""
    model, params = model_and_params
    d = str(tmp_path / "artifact")
    save_serving_artifact(d, params, step=0)
    other = TransformerLM(TransformerConfig(
        vocab_size=61, d_model=48, n_heads=4, n_layers=2, d_ff=64,
        max_seq_len=32, dtype=jnp.float32))
    probe = jax.random.randint(jax.random.PRNGKey(2), (1, 4), 0, 61)
    other_params = other.init(jax.random.PRNGKey(3), probe)["params"]
    with pytest.raises(Exception, match="shapes|cover"):
        load_serving_params(d, jax.eval_shape(lambda: other_params))


# ---- serve knobs ride the env registry ------------------------------------


def test_serve_config_from_env(monkeypatch):
    monkeypatch.setenv("BAGUA_SERVE_MAX_SLOTS", "5")
    monkeypatch.setenv("BAGUA_SERVE_PAGE_SIZE", "8")
    monkeypatch.setenv("BAGUA_SERVE_QUEUE_DEPTH", "17")
    cfg = ServeConfig.from_env(max_seq_len=64)
    assert cfg.max_slots == 5 and cfg.page_size == 8
    assert cfg.queue_depth == 17
    # num_pages auto-sizes to max_slots full-length sequences + reserved
    assert cfg.num_pages == 2 + 5 * (64 // 8)


def test_request_latency_fields():
    req = Request(rid=0, prompt=np.array([1]), max_new_tokens=3)
    assert req.ttft_s is None and req.tpot_s is None
    req.t_submit, req.t_first_token, req.t_done = 1.0, 1.5, 2.5
    req.output = [1, 2, 3]
    assert req.ttft_s == pytest.approx(0.5)
    assert req.tpot_s == pytest.approx(0.5)
