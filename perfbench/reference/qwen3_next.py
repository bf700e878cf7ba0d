"""Plain reference for the ``qwen3_next`` builder: Qwen3-Next-80B-A3B's
decoder (``model_type: qwen3_next``), next-token loss and AdamW in
``jax.numpy`` and float32, from the catalog row's ``config``
(Qwen/Qwen3-Next-80B-A3B-Instruct) and, where it is silent, from what the
configuration file lists under ``assumed``.  Imports nothing from
``bagua_tpu``; no kernel, no chunked form, no sort, no grouped matmul.  The
pieces every decoder reference shares (RMSNorm, rotate-half RoPE, top-k by
argmax, AdamW written out) are ``reference/olmoe.py``'s, loaded by file
name.

``N(x)`` is RMSNorm with scale ``1 + w`` (``w`` starts at zero).  Layer
``i`` (0-based) is full attention where ``(i + 1) % 4 == 0``, else linear
attention: ``x <- x + Mixer(N_1(x)); x <- x + MoE(N_2(x))``; then a final
``N`` and an untied head.

**Linear attention** (gated delta rule; 16 key heads, 32 value heads, 128
lanes each; value head ``h`` reads key head ``h // 2``).  ``u = N_1(x)``:

    [q, k, v, z] = u W_qkvz (2048, 2048, 4096, 4096);   [b, a] = u W_ba (32 + 32)
    [q, k, v] <- silu(causal depthwise convolution of 4 taps, no bias)
    beta_t = sigmoid(b_t);  alpha_t = exp(-exp(A_log) * softplus(a_t + dt_bias))
    q_t, k_t <- x / sqrt(sum x^2 + 1e-6) over a head's lanes;  q_t <- q_t / sqrt(128)
    S_t = alpha_t S_{t-1} + beta_t k_t (v_t - alpha_t S_{t-1}^T k_t)^T,   S_0 = 0
    o_t = S_t^T q_t
    y_t = w_n * o_t / rms(o_t) * silu(z_t)    (a head's 128 lanes, plain scale)
    Mixer = y W_out

computed exactly so: one position after the other (:func:`delta_rule`), a
``lax.scan`` inside a ``lax.scan`` over blocks of ``SCAN_BLOCK`` positions
whose inner steps are re-computed in the backward pass, so that what is
kept at 4,096 positions is a state a block and not a state a position.

**Full attention** (16 query heads over 2 key / value heads of 256):

    [q, gate] = u W_q (a head's 512 outputs: 256 | 256);  k = u W_k;  v = u W_v
    q, k <- N over each head's 256 lanes (one [256] scale each, 1 + w)
    rotate-half RoPE at theta 1e7 over lanes 0..63 of each head, 64..255 as they are
    Mixer = (softmax(q k^T / sqrt(256), causal) v * sigmoid(gate)) W_o

**Experts.**  ``pi = softmax(m W_r)`` over all 512 in float32, the 10
largest renormalised to sum to 1; SiLU-gated experts of width 512; beside
them ONE shared expert of width 512 whose output is multiplied by
``sigmoid(m w_s)``.  **The share** (``deployment``): of the 512 experts the
chip holds ``held`` from ``first_expert`` on; the sum runs over the winners
held here, with the weights renormalised over all ten, plus the whole
shared expert (``hyper["shared"]``: a test that adds ranks' shares up
counts it once), and that partial result goes on, here and in the program
alike.

``hyper`` carries switches that are all on in the architecture and that the
tests (``tests/test_qwen3_next.py``) and ``perfbench/tools/
qwen3_next_reference_check.py faults`` turn off one at a time, to show that
the comparison refuses a system that lacks the mechanism: ``decay`` (off:
alpha = 1), ``write_strength`` (off: beta = 1), ``l2_norm``, ``rotary_dim``
(None: the whole head rotates), ``attn_gate``, ``shared_gate``,
``zero_centered`` (off: scale ``w``), and ``scan_dtype`` (``"bfloat16"``: the
state and the decay of the scan kept in bfloat16, the nearest precision
below the float32 the configuration states for them).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from perfbench import cells

_shared = cells.load_plugin("reference", "olmoe")
rms_norm, rope = _shared.rms_norm, _shared.rope
_sdar = cells.load_plugin("reference", "sdar")
gradient_distance, parameter_change = (_sdar.gradient_distance,
                                       _sdar.parameter_change)
agree = _sdar.agree

#: Largest |trainer loss - reference loss| accepted on the three replayed
#: steps: ``reference/olmoe.py``'s, the limits of the harness's accepted
#: next-token cells of this size (uniform random targets over a slice of the
#: vocabulary, AdamW at 1e-4).  Readings (my chip runs, PR 50, v5e, published
#: widths, kernels on; PERF.md section 6): the system differs by at most
#: 0.0002 / 0.0005 / 0.0008 on the three steps over its seeds, fifteen to
#: thirty-seven times inside the limits; weights rounded to bfloat16 read
#: 0.0001 / 0.045 / 0.086 (refused on the second and third step), no
#: shared-expert gate 0.005 / 0.11 / 0.19.  The loss is NOT what sees the
#: attention's mechanisms (the whole head rotated: 0.0005 on every step;
#: no output gate 0.0006 / 0.009 / 0.021, inside): under uniform random
#: targets a change of the logits that is not aligned with the targets
#: averages out of the mean.  ``GRADIENT_TOLERANCE`` sees those.
LOSS_TOLERANCE = _shared.LOSS_TOLERANCE

#: Largest relative distance ``|g_system - g_reference| / |g_reference|``
#: (Frobenius norms) accepted on a ``watched`` leaf of the FIRST gradient of
#: the replay batch: the loss function the trainer's step differentiates
#: (``lm_loss_fn`` of the model as timed: bfloat16 products, the ``gdn_*``,
#: flash and grouped-matmul kernels forward and backward, the cell's 8,192
#: rows) against this file's float32 gradient.  It is the comparison that
#: sees each gate: a gradient keeps the direction that the mean loss
#: averages away.  Two readings (my chip runs, PR 50, as above): the system
#: reads 0.015 to 0.037 on every matrix and the convolution's taps and up
#: to 0.050 on the 32-entry vectors ``A_log`` and ``dt_bias`` (which vary
#: most by the seed) over ten seeds, the routers apart; against a reference
#: whose whole head rotates it reads 0.95 (the full layer's q), without the
#: output gate or without the shared expert's gate the reference's own
#: gradient of that gate is zero and the distance has no value (refused),
#: and at tiny widths (``tests/test_qwen3_next.py``) alpha = 1, beta = 1, no
#: L2 norm and plain-``w`` norm scales each read past this limit on the
#: leaf they touch.  The limit is 2.4 times the system's largest reading.
#: What it does NOT tell apart, by measurement: the scan's state and decay
#: kept in bfloat16 in the reference move these distances by 0.000 to 0.003
#: (``A_log`` 0.0213 -> 0.0239) — at the family's initialisation (``A ~
#: U(0, 16)``, ``dt_bias`` 1) a head forgets within a few positions, so the
#: state's precision hardly reaches the gradient; the kernels' float32
#: state is held by the tier-1 tests at decays near 1 instead
#: (``test_the_chunked_rule_is_the_per_token_scan``), and a lower precision
#: of the trainer's own state by ``CHANGE_TOLERANCE``.
GRADIENT_TOLERANCE = 0.12

#: The routers' limit: their gradient is a difference of terms that nearly
#: cancel (the renormalised top-10's Jacobian against the held experts'
#: outputs, which the system has in bfloat16), and the system reads 0.080
#: to 0.142 on the four layers' routers over ten seeds where every matrix
#: reads under 0.04.  2.1 times the largest reading; a router that scored or
#: weighed otherwise reads on the experts' and the shared expert's leaves
#: as well.
ROUTER_GRADIENT_TOLERANCE = 0.3
ROUTER = "mlp/router/kernel"

#: Largest relative distance accepted on a ``watched`` leaf (but those of
#: ``CHANGE_SKIPPED``) and any leaf of ``CHANGE_ALSO`` between the system's
#: and the reference's CHANGE of the parameters over the replayed updates
#: (``reference/sdar.py`` has the definition; a state left as it was reads
#: 1).  It holds what the first gradient cannot: the precision of the
#: trainer's weights and moments, and the three updates.  Two readings (my
#: chip runs, PR 50, eleven seeds): the system reads 0.177 to 0.258 on the
#: routers and 0.03 to 0.10 on every other leaf compared; the reference
#: with its weights rounded to bfloat16 at the start and after every update
#: reads 0.44 to 0.51 on the matrices, 0.26 on the out-projections and 1.04
#: on the convolutions' taps (bfloat16 does not hold their steps).  The
#: limit lies between the system's reading and 1, what a state left
#: unchanged reads, with the more room above the reading (fresh seeds read
#: higher): twice the system's largest, half of 1.
CHANGE_TOLERANCE = 0.5

#: not compared in the change: the linear layers' 32- and 128-entry vectors.
#: AdamW's first steps move an entry by the learning rate along its
#: gradient's SIGN, and an entry whose gradient is zero but for rounding
#: goes one way in the system and the other in the reference: one such entry
#: of 32 is a distance of 0.35 by itself.  Read over eleven seeds: 0.007 to
#: 0.38 (``dt_bias``), 0.007 to 0.28 (``A_log``), 0.002 to 0.16 (the gated
#: norm's scale): the seed's draw, not the system's precision.  Their first
#: gradient is compared (0.015 to 0.050)
CHANGE_SKIPPED = ("linear_attn/A_log", "linear_attn/dt_bias",
                  "linear_attn/norm")

#: compared in the parameters' change besides: the head, and a norm's scale
#: (zero-centred here: it starts at 0 and bfloat16 would hold its steps, so
#: what it guards is the update itself, not the storage)
CHANGE_ALSO = ("final_norm/scale", "lm_head/kernel")

#: the leaves compared, by the end of their path in the program's tree: every
#: gate the architecture adds, the matrices that feed them, and the mixers'
#: projections.  ``attn/q/kernel`` is compared as its two halves, the
#: queries' and the output gate's (:func:`watched`)
WATCHED_ENDS = (
    "linear_attn/A_log", "linear_attn/dt_bias", "linear_attn/conv",
    "linear_attn/norm", "linear_attn/in_proj_qkvz/kernel",
    "linear_attn/in_proj_ba/kernel", "linear_attn/out_proj/kernel",
    "attn/k/kernel", "attn/v/kernel", "attn/o/kernel",
    "mlp/shared_gate/kernel", "mlp/router/kernel", "mlp/shared_wi/kernel",
)

#: rows per chunk of the head's cross-entropy; positions per block of the
#: delta rule's scan
HEAD_CHUNK = 1024
SCAN_BLOCK = 64
L2_EPS = 1e-6


def norm(x, w, hyper):
    """``N``: RMSNorm with scale ``1 + w`` (fault off: ``w``)."""
    return rms_norm(x, 1.0 + w if hyper["zero_centered"] else w,
                    hyper["rms_norm_eps"])


# ---- linear attention --------------------------------------------------------


def causal_conv(x, taps):
    """``y_t = sum_j taps[j] x_{t - (n - 1 - j)}`` a channel, zeros in front:
    ``x`` [batch, seq, channels], ``taps`` [n, channels]."""
    n, seq = taps.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (n - 1, 0), (0, 0)))
    return sum(padded[:, j:j + seq] * taps[j] for j in range(n))


def delta_rule(q, k, v, alpha, beta, scan_dtype=jnp.float32):
    """The recurrence, position by position.  ``q`` / ``k`` / ``v``: [batch,
    seq, heads, 128] (keys and queries already repeated to the value heads);
    ``alpha`` / ``beta``: [batch, seq, heads].  -> o like ``v``."""
    batch, seq, heads, d_v = v.shape
    block = math.gcd(seq, SCAN_BLOCK)

    def step(state, x):
        q_t, k_t, v_t, a_t, b_t = x
        state = (state * a_t.astype(scan_dtype)[..., None, None]).astype(
            scan_dtype)
        read = jnp.einsum("bhkv,bhk->bhv", state.astype(jnp.float32), k_t)
        delta = b_t[..., None] * (v_t - read)
        state = (state.astype(jnp.float32)
                 + k_t[..., :, None] * delta[..., None, :]).astype(scan_dtype)
        return state, jnp.einsum("bhkv,bhk->bhv", state.astype(jnp.float32),
                                 q_t)

    @jax.checkpoint
    def one_block(state, xs):
        return jax.lax.scan(step, state, xs)

    def blocks(t):          # [batch, seq, ...] -> [seq / block, block, batch, ...]
        t = jnp.moveaxis(t, 1, 0)
        return t.reshape(seq // block, block, *t.shape[1:])

    state = jnp.zeros((batch, heads, q.shape[-1], d_v), scan_dtype)
    _, o = jax.lax.scan(one_block, state,
                        tuple(blocks(t) for t in (q, k, v, alpha, beta)))
    return jnp.moveaxis(o.reshape(seq, batch, heads, d_v), 0, 1)


def l2_normalize(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def linear_attention(u, p, hyper):
    """The linear-attention mixer on ``u`` [batch, seq, d] (no residual)."""
    batch, seq, _ = u.shape
    hk, hv = hyper["linear_key_heads"], hyper["linear_value_heads"]
    dk, dv = hyper["linear_key_dim"], hyper["linear_value_dim"]
    key_width, value_width = hk * dk, hv * dv
    qkvz = u @ p["in_proj_qkvz"]["kernel"]
    ba = u @ p["in_proj_ba"]["kernel"]
    mixed = jax.nn.silu(causal_conv(
        qkvz[..., :2 * key_width + value_width], p["conv"]))
    z = qkvz[..., 2 * key_width + value_width:].reshape(batch, seq, hv, dv)
    q = mixed[..., :key_width].reshape(batch, seq, hk, dk)
    k = mixed[..., key_width:2 * key_width].reshape(batch, seq, hk, dk)
    v = mixed[..., 2 * key_width:].reshape(batch, seq, hv, dv)
    if hyper["l2_norm"]:
        q, k = l2_normalize(q), l2_normalize(k)
    q = q / math.sqrt(dk)
    q, k = (jnp.repeat(t, hv // hk, axis=2) for t in (q, k))
    beta = jax.nn.sigmoid(ba[..., :hv])
    alpha = jnp.exp(-jnp.exp(p["A_log"])
                    * jax.nn.softplus(ba[..., hv:] + p["dt_bias"]))
    if not hyper["decay"]:
        alpha = jnp.ones_like(alpha)
    if not hyper["write_strength"]:
        beta = jnp.ones_like(beta)
    o = delta_rule(q, k, v, alpha, beta, jnp.dtype(hyper["scan_dtype"]))
    y = rms_norm(o, p["norm"], hyper["rms_norm_eps"]) * jax.nn.silu(z)
    return y.reshape(batch, seq, value_width) @ p["out_proj"]["kernel"]


# ---- full attention ---------------------------------------------------------


def causal_attention(q, k, v):
    """Causal softmax attention with grouped key / value heads:
    ``reference/sdar.py``'s attention (one query head and one block of
    queries at a time) under the dense lower-triangular mask."""
    seq = q.shape[1]
    return _sdar.attention(q, k, v, jnp.tril(jnp.ones((seq, seq), bool)))


def rotate(x, hyper):
    """RoPE over the first ``rotary_dim`` lanes of each head (fault: None,
    the whole head)."""
    part = hyper["rotary_dim"]
    if part is None:
        return rope(x, hyper["rope_theta"])
    return jnp.concatenate([rope(x[..., :part], hyper["rope_theta"]),
                            x[..., part:]], axis=-1)


def full_attention(u, p, hyper):
    """The gated softmax-attention mixer on ``u`` [batch, seq, d]."""
    batch, seq, d = u.shape

    def project(name):
        # the program's kernel is [d, heads, width]
        kernel = p[name]["kernel"]
        return (u @ kernel.reshape(d, -1)).reshape(batch, seq,
                                                   *kernel.shape[1:])

    head_dim = p["k"]["kernel"].shape[-1]
    q_gate = project("q")
    q, gate = q_gate[..., :head_dim], q_gate[..., head_dim:]
    q = rotate(norm(q, p["q_norm"]["scale"], hyper), hyper)
    k = rotate(norm(project("k"), p["k_norm"]["scale"], hyper), hyper)
    o = causal_attention(q, k, project("v"))
    if hyper["attn_gate"]:
        o = o * jax.nn.sigmoid(gate)
    return o.reshape(batch, seq, -1) @ p["o"]["kernel"].reshape(-1, d)


# ---- experts ----------------------------------------------------------------


def moe(m, p, hyper):
    """The held experts' part of the expert layer on ``m`` [tokens, d]
    (``reference/sdar.py``'s: softmax over all experts, the winners
    renormalised, the sum over the winners held here), and the shared
    expert."""
    out = _sdar.moe(m, p, hyper)
    if not hyper["shared"]:
        return out
    shared = (jax.nn.silu(m @ p["shared_wg"]["kernel"])
              * (m @ p["shared_wi"]["kernel"])) @ p["shared_wo"]["kernel"]
    if hyper["shared_gate"]:
        shared = jax.nn.sigmoid(m @ p["shared_gate"]["kernel"]) * shared
    return out + shared


# ---- the model --------------------------------------------------------------


def is_linear(layer: int, hyper: dict) -> bool:
    return (layer + 1) % hyper["full_attention_interval"] != 0


def block(x, p, layer: int, hyper: dict):
    batch, seq, d = x.shape
    if is_linear(layer, hyper):
        x = x + linear_attention(
            norm(x, p["linear_attn_norm"]["scale"], hyper), p["linear_attn"],
            hyper)
    else:
        x = x + full_attention(norm(x, p["attn_norm"]["scale"], hyper),
                               p["attn"], hyper)
    m = norm(x, p["mlp_norm"]["scale"], hyper)
    return x + moe(m.reshape(batch * seq, d), p["mlp"], hyper).reshape(
        batch, seq, d)


def hidden_states(params: dict, inputs, hyper: dict):
    """Final-norm hidden states [batch, seq, d]."""
    x = params["embed"]["embedding"][inputs]
    for layer in range(hyper["layers"]):
        # a layer's activations are alive only while its own backward runs
        x = jax.checkpoint(functools.partial(block, layer=layer, hyper=hyper))(
            x, params[f"block_{layer}"])
    return norm(x, params["final_norm"]["scale"], hyper)


def logits_fn(params: dict, inputs, hyper: dict):
    return hidden_states(params, inputs, hyper) @ params["lm_head"]["kernel"]


def loss_fn(params: dict, tokens, hyper: dict) -> jax.Array:
    """Mean next-token cross-entropy of ``tokens`` [batch, seq + 1] over the
    held slice of the vocabulary."""
    x = hidden_states(params, tokens[:, :-1], hyper)
    head = params["lm_head"]["kernel"]
    rows = x.reshape(-1, x.shape[-1])
    targets = tokens[:, 1:].reshape(-1)
    chunk = math.gcd(rows.shape[0], HEAD_CHUNK)

    @jax.checkpoint
    def chunk_loss(piece):
        xs, ts = piece
        logp = jax.nn.log_softmax(xs @ head, axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, ts[:, None], axis=-1))

    sums = jax.lax.map(chunk_loss, (rows.reshape(-1, chunk, rows.shape[-1]),
                                    targets.reshape(-1, chunk)))
    return jnp.sum(sums) / rows.shape[0]


def hyperparameters(config: dict) -> dict:
    """What the equations need, from a configuration file that keeps the
    source's key names; the share from its ``deployment``."""
    held = int(config["num_experts"])
    head_dim = int(config["head_dim"])
    return {
        "layers": int(config["num_hidden_layers"]),
        "full_attention_interval": int(config["full_attention_interval"]),
        "linear_key_heads": int(config["linear_num_key_heads"]),
        "linear_value_heads": int(config["linear_num_value_heads"]),
        "linear_key_dim": int(config["linear_key_head_dim"]),
        "linear_value_dim": int(config["linear_value_head_dim"]),
        "experts_per_token": int(config["num_experts_per_tok"]),
        "first_expert": int(config["deployment"]["expert_rank"]) * held,
        "rope_theta": float(config["rope_theta"]),
        "rotary_dim": int(head_dim * float(config["partial_rotary_factor"])),
        "rms_norm_eps": float(config["rms_norm_eps"]),
        "decay": True, "write_strength": True, "l2_norm": True,
        "attn_gate": True, "shared": True, "shared_gate": True,
        "zero_centered": True, "scan_dtype": "float32",
    }


@functools.partial(jax.jit, static_argnames=("hyper",))
def _loss_and_grads(params, tokens, *, hyper):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss_fn)(params, tokens, dict(hyper))


def watched(tree: dict, also: tuple = ()) -> dict:
    """``{"block_0/linear_attn/A_log": leaf, ...}``: the leaves of a tree in
    the program's layout (parameters or their gradients) whose path ends in
    one of ``WATCHED_ENDS``, the two halves of every ``attn/q/kernel`` ([d,
    heads, 2 head_dim]: queries | output gate) under names of their own,
    and the leaves named in ``also``."""
    flat = {"/".join(str(k.key) for k in path): leaf
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree)}
    out = {name: leaf for name, leaf in flat.items()
           if name in also or name.endswith(WATCHED_ENDS)}
    for name, leaf in flat.items():
        if name.endswith("attn/q/kernel") and "linear_attn" not in name:
            half = leaf.shape[-1] // 2
            out[name + "[query]"] = leaf[..., :half]
            out[name + "[gate]"] = leaf[..., half:]
    return out


watched_copy = jax.jit(lambda tree: jax.tree.map(
    jnp.copy, watched(tree, CHANGE_ALSO)))


def replay_losses(params: dict, batch: dict, steps: int, optimizer: dict,
                  hyper: dict, round_weights=None,
                  first_gradient=None, last_change=None) -> list[float]:
    """Train ``steps`` AdamW steps on the one ``batch`` ({"tokens": [b, seq +
    1]}) from the program-layout ``params`` (float32) and return the loss
    seen at each step (before its update), as floats.  ``params`` is not
    kept.  ``round_weights`` / ``first_gradient`` / ``last_change``: as in
    ``reference/sdar.py``."""
    adamw = _shared.adamw_hyperparameters(optimizer)
    start = watched_copy(params) if last_change is not None else None
    if round_weights is not None:
        params = round_weights(params)
    moments = None
    tokens = jnp.asarray(batch["tokens"])
    frozen = tuple(sorted(hyper.items()))
    losses = []
    for step in range(1, steps + 1):
        loss, grads = _loss_and_grads(params, tokens, hyper=frozen)
        losses.append(loss)
        if step == 1 and first_gradient is not None:
            first_gradient(watched(grads))
        if moments is None:
            moments = (jax.tree.map(jnp.zeros_like, params),
                       jax.tree.map(jnp.zeros_like, params))
        params, moments = _shared._adamw(
            params, moments, grads, jnp.int32(step), n_micro=1, **adamw)
        del grads
        if step < steps:
            # the moments wait on the host while the next gradient is made
            moments = jax.device_get(moments)
        if round_weights is not None:
            params = round_weights(params)
    del moments
    if last_change is not None:
        last_change(parameter_change(start, watched(params, CHANGE_ALSO)))
    return [float(x) for x in losses]


def gradients_agree(distances: dict, tolerance: float = GRADIENT_TOLERANCE,
                    router_tolerance: float = ROUTER_GRADIENT_TOLERANCE
                    ) -> bool:
    """Whether every watched leaf of the system's first gradient is within
    its limit of the reference's (and there is one, and all finite): a
    router's within ``router_tolerance``, every other leaf within
    ``tolerance``."""
    return bool(distances) and all(
        math.isfinite(d) and d <= (router_tolerance if name.endswith(ROUTER)
                                   else tolerance)
        for name, d in distances.items())


def changes_agree(distances: dict,
                  tolerance: float = CHANGE_TOLERANCE) -> bool:
    """Whether every leaf's change over the replayed updates is within
    ``tolerance`` of the reference's (and there is one, and all finite: a
    leaf the reference did not move at all has no distance); the leaves of
    ``CHANGE_SKIPPED`` are not held."""
    held = {name: d for name, d in distances.items()
            if not name.endswith(CHANGE_SKIPPED)}
    return gradients_agree(held, tolerance, tolerance)
