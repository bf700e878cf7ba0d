"""Plain reference for the ``nemotron_h`` builder: NVIDIA-Nemotron-3-Nano-30B-
A3B's decoder (``model_type: nemotron_h``), next-token loss and AdamW in
``jax.numpy`` and float32, from the catalog row's ``config``
(nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16) and, where it is silent, from
what the configuration file lists under ``assumed``.  Imports nothing from
``bagua_tpu``; no kernel, no chunked form, no sort, no grouped matmul.  The
pieces every decoder reference shares (RMSNorm, top-k by argmax, AdamW
written out, attention a head and a block of queries at a time, the
distances) are ``reference/olmoe.py``'s and ``reference/sdar.py``'s, loaded
by file name.

``N(x)`` is RMSNorm at eps 1e-5, plain scale ``w`` (ones at the start).
Layer ``i`` is of the kind ``hybrid_override_pattern[i]`` — ``M`` Mamba-2,
``E`` experts, ``*`` attention — and **a block is one sub-layer**: ``x <- x +
F_i(N_i(x))``; then a final ``N`` and an untied head.  No positional encoding
anywhere.

**Mamba-2** (64 heads of 64, 8 groups, state 128; head ``h`` reads group ``h
// 8``).  ``u = N(x)``:

    [z | xBC | dt] = u W_in                       (4096, 6144, 64 columns)
    xBC = silu(causal depthwise convolution of 4 taps + bias)
    [x | B | C] = xBC                             (4096, 1024, 1024 columns)
    delta_t = softplus(dt_t + dt_bias);  a_t = exp(-exp(A_log) delta_t)
    S_t = a_t S_{t-1} + delta_t x_t B_t^T,   S_0 = 0   (a head's [64, 128])
    y_t = S_t C_t + D x_t
    g = y * silu(z);  o = w_n * g / sqrt(mean_512(g^2) + 1e-5)   a GROUP's lanes
    F = o W_out

computed exactly so: one position after the other (:func:`state_space_scan`),
a ``lax.scan`` inside a ``lax.scan`` over blocks of ``SCAN_BLOCK`` positions
whose inner steps are re-computed in the backward pass, so that what is kept
at 8,192 positions is a state a block and not a state a position.

**Attention** (32 query heads over 2 key / value heads of 128): ``q = u
W_q``, ``k = u W_k``, ``v = u W_v``, no bias, NO rotation, causal softmax at
scale ``128^-1/2``, query head ``i`` reads key / value head ``i // 16``; ``F
= attn W_o``.

**Experts** (128 routed, 6 a token, 1 shared).  ``s = sigmoid(m W_r)`` over
all 128 in float32; the choice is the 6 largest of ``s + b`` (``b``: the
score-correction bias); the weights are ``s`` WITHOUT ``b`` at the winners,
divided by their sum + 1e-20, times 2.5.  An expert is ``relu(m W_up)^2
W_down`` at width 1856, no gate matrix.  The shared expert is the same form
at width 3712, for every token, added to the routed sum, no gate.  **The
share** (``deployment``): of the 128 experts the chip holds ``held`` from
``first_expert`` on; the sum runs over the winners held here, with the
weights renormalised over all six and scaled, plus the whole shared expert
(``hyper["shared"]``: a test that adds ranks' shares up counts it once), and
that partial result goes on, here and in the program alike.

``hyper`` carries switches that are all set as the architecture has them and
that the tests (``tests/test_nemotron_h.py``) and ``perfbench/tools/
nemotron_h_reference_check.py`` turn one at a time, to show that the
comparison refuses a system that lacks the mechanism: ``softplus``,
``decay`` (off: ``a_t`` = 1), ``skip`` (off: no ``D x``), ``gate_first``
(off: the norm before the gate), ``norm_groups`` (1: one norm over all 4096
lanes), ``head_group`` (``"strided"``: head ``h`` reads group ``h % 8``),
``conv_bias``, ``router_score`` (``"softmax"``), ``bias_in_weights``,
``routed_scale`` (1.0), ``renormalise``, ``activation`` (``"relu"``),
``rotate`` (rotate-half RoPE at ``rope_theta`` on q and k), ``sublayers``
(2: every block applies its sub-layer twice), and ``scan_dtype``
(``"bfloat16"``: the state and the decay of the scan kept in bfloat16, the
nearest precision below the float32 the configuration states for them).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from perfbench import cells

_shared = cells.load_plugin("reference", "olmoe")
rms_norm, rope, top_k_by_argmax = (_shared.rms_norm, _shared.rope,
                                   _shared.top_k_by_argmax)
_sdar = cells.load_plugin("reference", "sdar")
parameter_change, agree = _sdar.parameter_change, _sdar.agree

#: Largest |trainer loss - reference loss| accepted on the three replayed
#: steps: ``reference/olmoe.py``'s, the limits of the harness's accepted
#: next-token cells of this size (uniform random targets over a slice of the
#: vocabulary, AdamW at 1e-4).  Readings (my chip runs, PR 54, v5e, published
#: widths, kernels on; PERF.md section 6): the system differs by at most
#: 0.0011 / 0.0012 / 0.0018 on the three steps over its ten seeds, three to
#: sixteen times inside the limits; weights rounded to bfloat16 at the start
#: and after every update (the nearest precision below the float32 the
#: configuration states) read 0.0003 / 0.048 / 0.091: refused on the second
#: and third step.  The loss is NOT what sees the router's bias in the
#: weights (0.0003 / 0.0037 / 0.0040, inside) nor a bfloat16 state in the
#: scan (0.0002 / 0.0013 / 0.0003): the first is the score bias's own
#: gradient's to see (``SCORE_BIAS``), the second nothing here sees (below).
LOSS_TOLERANCE = _shared.LOSS_TOLERANCE

#: Largest relative distance ``|g_system - g_reference| / |g_reference|``
#: (Frobenius norms) accepted on a ``watched`` leaf of the FIRST gradient of
#: the replay batch: the loss function the trainer's step differentiates
#: (``lm_loss_fn`` of the model as timed: bfloat16 products, the ``ssd_*``,
#: flash and grouped-matmul kernels forward and backward, the cell's 8,192
#: rows) against this file's float32 gradient.  It is the comparison that
#: sees each mechanism: a gradient keeps the direction that the mean loss
#: averages away.  Two readings (my chip runs, PR 54, as above): the system
#: reads 0.030 to 0.102 on every leaf compared but the routers, over its
#: ten seeds — nearly one number for all of them, 0.045 to 0.055 on the
#: matrices of every block from the first on: a routed (row, expert) pair
#: whose sixth and seventh scores are all but tied goes one way under
#: bfloat16 rows and the other under float32 ones, and what a flipped pair
#: adds or withholds reaches every leaf —; at tiny widths and float32
#: (``tests/test_nemotron_h.py``) each of fifteen faults moves the logits
#: by far more than rounding, and a bias that leaks into the weights reads
#: 1.0 on the bias's own leaf (``SCORE_BIAS``), a scan without its decay has
#: no finite value on ``A_log``.  The limit is 2.5 times the system's
#: largest reading.  What it does NOT tell apart, by measurement:
#: the scan's state and decay kept in bfloat16 in the reference read 0.046
#: to 0.054 where float32 reads 0.046 to 0.054 (every leaf within 0.003,
#: ``A_log`` 0.070 -> 0.093) — at the family's initialisation (step sizes of
#: 1e-3 to 0.1 against ``A`` of 1 to 64) most heads forget within a few
#: positions, and the ``D x`` skip carries the rest; the kernels' float32
#: state is held by the tier-1 tests at decays near 1 instead
#: (``test_the_chunked_scan_is_the_per_token_scan[hardly_decays]``), and a
#: lower precision of the trainer's own state by ``CHANGE_TOLERANCE``.  Nor
#: are the routed experts' own matrices compared: a held expert sees some 384
#: rows a step, the flipped pairs are a few of a hundred of them, and its
#: gradient reads 0.03 to 0.27 by the layer and the seed (the shared
#: expert's, over all 8,192 rows, 0.035 to 0.051).
GRADIENT_TOLERANCE = 0.25

#: The routers' limit: their gradient is a difference of terms that nearly
#: cancel (the renormalised top-6's Jacobian against the held experts'
#: outputs, which the system has in bfloat16) over the third of the rows
#: that have a winner held here, and the system reads 0.119 to 0.294 on the
#: four layers' routers over its ten seeds where every matrix reads under
#: 0.11.  Twice the largest reading; a router that scored or weighed otherwise
#: reads on the shared expert's and the mixers' leaves as well.
ROUTER_GRADIENT_TOLERANCE = 0.6
ROUTER = "mlp/router/kernel"

#: The score bias enters the CHOICE alone, so its gradient is exactly zero,
#: in the program and here: compared as ``|got - want|`` over ``|want|``
#: where the reference's is not zero and over 1 where it is
#: (:func:`gradient_distance`).  A system or a reference whose bias leaks
#: into the weights has a gradient there, and reads 1 or more against the
#: one that has none.
SCORE_BIAS = "mlp/score_bias"

#: Largest relative distance accepted on a compared leaf between the
#: system's and the reference's CHANGE of the parameters over the replayed
#: updates (``reference/sdar.py`` has the definition; a state left as it was
#: reads 1).  It holds what the first gradient cannot: the precision of the
#: trainer's weights and moments, and the three updates.  Two readings (my
#: chip runs, PR 54): the system reads 0.05 to 0.180 on every leaf compared
#: but the routers, over its ten seeds; the reference with its weights rounded to bfloat16 at
#: the start and after every update reads 0.28 to 0.46 on the matrices, 1.1
#: on the convolutions' taps and bias and has no value on the norms' scales
#: (bfloat16 does not hold their steps: refused).  The limit lies between
#: the system's reading and 1, what a state left unchanged reads, with the
#: more room above the reading (fresh seeds read higher): 2.8 times the
#: system's largest, half of 1.
CHANGE_TOLERANCE = 0.5

#: The routers' change: 0.25 to 0.40 over the ten seeds (their first gradient
#: is the noisiest, and AdamW's first steps follow its sign); between that
#: reading and 1 with the more room above it.  Rounded weights read 0.46
#: to 0.53 there: it is the matrices' limit that refuses them.
ROUTER_CHANGE_TOLERANCE = 0.7

#: not compared in the change: the state-space layers' 64-entry vectors and
#: the score bias.  AdamW's first steps move an entry by the learning rate
#: along its gradient's SIGN, and an entry whose gradient is zero but for
#: rounding goes one way in the system and the other in the reference: read
#: over the seeds 0.03 to 0.23 (``A_log``, ``dt_bias``) and 0.04 to 0.22
#: (``D``): the seed's draw, not the system's precision
#: (``reference/qwen3_next.py::CHANGE_SKIPPED`` has the same for its 32-entry
#: vectors).  Their first gradient is compared.  The score bias moves by the
#: optimizer's decay alone, a hundred-millionth of itself a step: under a
#: float32's last digit
CHANGE_SKIPPED = ("ssm/A_log", "ssm/dt_bias", "ssm/D", SCORE_BIAS)

#: compared in the parameters' change besides: the head and the final norm
CHANGE_ALSO = ("final_norm/scale", "lm_head/kernel")

#: the leaves compared, by the end of their path in the program's tree: every
#: leaf the architecture adds and the matrices that feed them.  The large
#: matrices are compared on a SAMPLE of ``SAMPLE`` columns, rows or heads,
#: not whole: the comparison's copies
#: live on the chip beside the trainer's state, its step's reservation and
#: one more float32 copy of the parameters, and with every matrix whole (2.3
#: GiB) the chip ran out (my chip run, PR 54: ``unstack_params`` asked for 38
#: MB and 23 were free).  A fault or a lower precision moves a matrix's
#: columns alike; the sample is 0.26 GiB.  ``ssm/in_proj`` is compared as its
#: three parts, ``[z]``, ``[xBC]`` (x's sample, B and C whole) and the 64
#: ``[dt]`` columns, which the whole leaf's norm would hide (:func:`watched`)
WATCHED_ENDS = (
    "ssm/A_log", "ssm/dt_bias", "ssm/D", "ssm/conv", "ssm/conv_bias",
    "ssm/norm", "ssm/out_proj/kernel",
    "attn/q/kernel", "attn/k/kernel", "attn/v/kernel", "attn/o/kernel",
    "mlp/router/kernel", "mlp/shared_wi/kernel", "mlp/shared_wo/kernel",
    SCORE_BIAS,
)
SAMPLE = 512
SAMPLED_HEADS = 4

#: rows per chunk of the head's cross-entropy; positions per block of the
#: state-space scan
HEAD_CHUNK = 1024
SCAN_BLOCK = 64

KINDS = {"M": "ssm", "E": "moe", "*": "attn"}


def norm(x, w, hyper):
    return rms_norm(x, w, hyper["norm_eps"])


# ---- Mamba-2 -------------------------------------------------------------------


def causal_conv(x, taps, bias):
    """``y_t = sum_j taps[j] x_{t - (n - 1 - j)} + bias`` a channel, zeros in
    front: ``x`` [batch, seq, channels], ``taps`` [n, channels]."""
    n, seq = taps.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (n - 1, 0), (0, 0)))
    return sum(padded[:, j:j + seq] * taps[j] for j in range(n)) + bias


def state_space_scan(x, delta, alpha, b, c, scan_dtype=jnp.float32):
    """The recurrence, position by position.  ``x``: [batch, seq, heads, P];
    ``delta`` / ``alpha`` (the step size and the decay): [batch, seq,
    heads]; ``b`` / ``c``: [batch, seq, heads, N] (the groups' maps already
    laid over the heads).  -> ``S_t C_t`` like ``x``."""
    batch, seq, heads, width = x.shape
    block = math.gcd(seq, SCAN_BLOCK)

    def step(state, inputs):
        x_t, d_t, a_t, b_t, c_t = inputs
        state = (state * a_t.astype(scan_dtype)[..., None, None]).astype(
            jnp.float32)
        state = (state + (d_t[..., None] * x_t)[..., :, None]
                 * b_t[..., None, :]).astype(scan_dtype)
        return state, jnp.einsum("bhpn,bhn->bhp", state.astype(jnp.float32),
                                 c_t)

    @jax.checkpoint
    def one_block(state, xs):
        return jax.lax.scan(step, state, xs)

    def blocks(t):          # [batch, seq, ...] -> [seq / block, block, batch, ...]
        t = jnp.moveaxis(t, 1, 0)
        return t.reshape(seq // block, block, *t.shape[1:])

    state = jnp.zeros((batch, heads, width, b.shape[-1]), scan_dtype)
    _, y = jax.lax.scan(one_block, state,
                        tuple(blocks(t) for t in (x, delta, alpha, b, c)))
    return jnp.moveaxis(y.reshape(seq, batch, heads, width), 0, 1)


def mamba2(u, p, hyper):
    """The Mamba-2 mixer on ``u`` [batch, seq, d] (no residual)."""
    batch, seq, _ = u.shape
    heads, width = hyper["ssm_heads"], hyper["ssm_head_dim"]
    groups, state = hyper["ssm_groups"], hyper["ssm_state"]
    inner, maps = heads * width, groups * state
    zxbcdt = u @ p["in_proj"]
    z = zxbcdt[..., :inner]
    xbc = zxbcdt[..., inner:2 * inner + 2 * maps]
    dt = zxbcdt[..., 2 * inner + 2 * maps:]
    xbc = jax.nn.silu(causal_conv(
        xbc, p["conv"], p["conv_bias"] if hyper["conv_bias"] else 0.0))
    x = xbc[..., :inner].reshape(batch, seq, heads, width)
    b, c = (xbc[..., lo:lo + maps].reshape(batch, seq, groups, state)
            for lo in (inner, inner + maps))
    if hyper["head_group"] == "strided":          # fault: head h reads h % G
        b, c = (jnp.tile(t, (1, 1, heads // groups, 1)) for t in (b, c))
    else:                                         # head h reads h // (H / G)
        b, c = (jnp.repeat(t, heads // groups, axis=2) for t in (b, c))
    raw = dt + p["dt_bias"]
    delta = jax.nn.softplus(raw) if hyper["softplus"] else raw
    alpha = (jnp.exp(-jnp.exp(p["A_log"]) * delta) if hyper["decay"]
             else jnp.ones_like(delta))
    y = state_space_scan(x, delta, alpha, b, c,
                         jnp.dtype(hyper["scan_dtype"]))
    if hyper["skip"]:
        y = y + p["D"][:, None] * x
    y = y.reshape(batch, seq, inner)

    def group_norm(t):
        per = hyper["norm_groups"]
        t = rms_norm(t.reshape(batch, seq, per, inner // per),
                     p["norm"].reshape(per, inner // per), hyper["norm_eps"])
        return t.reshape(batch, seq, inner)

    o = (group_norm(y * jax.nn.silu(z)) if hyper["gate_first"]
         else group_norm(y) * jax.nn.silu(z))
    return o @ p["out_proj"]["kernel"]


# ---- attention -------------------------------------------------------------------


def attention(u, p, hyper):
    """The softmax-attention mixer on ``u`` [batch, seq, d]: grouped key /
    value heads, no positional encoding (``reference/sdar.py``'s attention,
    one query head and one block of queries at a time, under the dense
    lower-triangular mask)."""
    batch, seq, d = u.shape

    def project(name):
        kernel = p[name]["kernel"]                # [d, heads, head_dim]
        return (u @ kernel.reshape(d, -1)).reshape(batch, seq,
                                                   *kernel.shape[1:])

    q, k = project("q"), project("k")
    if hyper["rotate"]:                           # fault: the family rotates none
        q, k = rope(q, hyper["rope_theta"]), rope(k, hyper["rope_theta"])
    o = _sdar.attention(q, k, project("v"),
                        jnp.tril(jnp.ones((seq, seq), bool)))
    return o.reshape(batch, seq, -1) @ p["o"]["kernel"].reshape(-1, d)


# ---- experts ---------------------------------------------------------------------


def activation(x, hyper):
    x = jax.nn.relu(x)
    return jnp.square(x) if hyper["activation"] == "relu2" else x


def moe(m, p, hyper):
    """The held experts' part of the expert layer on ``m`` [tokens, d] and
    the shared expert."""
    held = p["expert_wi"].shape[0]
    logits = m @ p["router"]["kernel"]
    scores = (jax.nn.sigmoid(logits) if hyper["router_score"] == "sigmoid"
              else jax.nn.softmax(logits, axis=-1))
    biased = scores + p["score_bias"]
    _, chosen = top_k_by_argmax(biased, hyper["experts_per_token"])
    weights = jnp.take_along_axis(
        biased if hyper["bias_in_weights"] else scores, chosen, axis=-1)
    if hyper["renormalise"]:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    weights = weights * hyper["routed_scale"]
    local = chosen - hyper["first_expert"]                 # [tokens, k]
    # one_hot of an id outside 0 .. held-1 is a zero row: a winner another
    # rank holds adds nothing here
    combine = jnp.einsum("tk,tke->te", weights,
                         jax.nn.one_hot(local, held, dtype=jnp.float32))

    @jax.checkpoint
    def expert_part(expert):
        w_up, w_down, weight = expert
        return weight[:, None] * (activation(m @ w_up, hyper) @ w_down)

    out, _ = jax.lax.scan(
        lambda out, expert: (out + expert_part(expert), None),
        jnp.zeros_like(m), (p["expert_wi"], p["expert_wo"], combine.T))
    if not hyper["shared"]:
        return out
    return out + (activation(m @ p["shared_wi"]["kernel"], hyper)
                  @ p["shared_wo"]["kernel"])


# ---- the model -------------------------------------------------------------------


def block(x, p, layer: int, hyper: dict):
    batch, seq, d = x.shape
    kind = KINDS[hyper["pattern"][layer]]
    for _ in range(hyper["sublayers"]):
        u = norm(x, p[f"{'mlp' if kind == 'moe' else kind}_norm"]["scale"],
                 hyper)
        if kind == "ssm":
            x = x + mamba2(u, p["ssm"], hyper)
        elif kind == "attn":
            x = x + attention(u, p["attn"], hyper)
        else:
            x = x + moe(u.reshape(batch * seq, d), p["mlp"], hyper).reshape(
                batch, seq, d)
    return x


def hidden_states(params: dict, inputs, hyper: dict):
    """Final-norm hidden states [batch, seq, d]."""
    x = params["embed"]["embedding"][inputs]
    for layer in range(len(hyper["pattern"])):
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
    pattern = str(config["hybrid_override_pattern"])
    if len(pattern) != int(config["num_hidden_layers"]):
        raise ValueError("hybrid_override_pattern does not name every layer")
    return {
        "pattern": pattern,
        "ssm_heads": int(config["mamba_num_heads"]),
        "ssm_head_dim": int(config["mamba_head_dim"]),
        "ssm_groups": int(config["n_groups"]),
        "ssm_state": int(config["ssm_state_size"]),
        "norm_groups": int(config["n_groups"]),
        "experts_per_token": int(config["num_experts_per_tok"]),
        "first_expert": (int(config["deployment"]["expert_rank"])
                         * int(config["n_routed_experts"])),
        "routed_scale": float(config["routed_scaling_factor"]),
        "renormalise": bool(config["norm_topk_prob"]),
        "activation": str(config["mlp_hidden_act"]),
        "norm_eps": float(config["layer_norm_epsilon"]),
        "rope_theta": float(config["rope_theta"]),
        "router_score": "sigmoid", "bias_in_weights": False,
        "softplus": True, "decay": True, "skip": True, "gate_first": True,
        "head_group": "blocked", "conv_bias": True, "rotate": False,
        "shared": True, "sublayers": 1, "scan_dtype": "float32",
    }


@functools.partial(jax.jit, static_argnames=("hyper",))
def _loss_and_grads(params, tokens, *, hyper):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(loss_fn)(params, tokens, dict(hyper))


def _sample(name: str, leaf):
    """The part of a watched leaf that is compared (``WATCHED_ENDS`` and
    ``CHANGE_ALSO``)."""
    if name.endswith(("out_proj/kernel", "shared_wo/kernel")):
        return leaf[:SAMPLE]                              # [width, d]: rows
    if name.endswith(("shared_wi/kernel", "lm_head/kernel")):
        return leaf[:, :SAMPLE]                           # [d, width]: columns
    if name.endswith("attn/q/kernel"):
        return leaf[:, :SAMPLED_HEADS]                    # [d, heads, head_dim]
    if name.endswith("attn/o/kernel"):
        return leaf[:SAMPLED_HEADS]                       # [heads, head_dim, d]
    return leaf


def watched(tree: dict, also: tuple = ()) -> dict:
    """``{"block_0/ssm/A_log": leaf, ...}``: the leaves of a tree in the
    program's layout (parameters or their gradients) whose path ends in one
    of ``WATCHED_ENDS`` (the large matrices by their sample, ``_sample``),
    the three parts of every ``ssm/in_proj`` ([d, z | xBC | dt]) under names
    of their own, and the leaves named in ``also``."""
    flat = {"/".join(str(k.key) for k in path): leaf
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree)}
    out = {name: _sample(name, leaf) for name, leaf in flat.items()
           if name in also or name.endswith(WATCHED_ENDS)}
    for name, leaf in flat.items():
        if name.endswith("ssm/in_proj"):
            # the sizes from the layer's own leaves: the gated norm's scale
            # is d_inner long, A_log one entry a head
            inner = flat[name[:-len("in_proj")] + "norm"].shape[0]
            heads = flat[name[:-len("in_proj")] + "A_log"].shape[0]
            xbc = leaf[:, inner:leaf.shape[1] - heads]
            out[name + "[z]"] = leaf[:, :min(SAMPLE, inner)]
            out[name + "[xBC]"] = jnp.concatenate(
                [xbc[:, :min(SAMPLE, inner)], xbc[:, inner:]], axis=1)
            out[name + "[dt]"] = leaf[:, leaf.shape[1] - heads:]
    return out


watched_copy = jax.jit(lambda tree: jax.tree.map(
    jnp.copy, watched(tree, CHANGE_ALSO)))


@jax.jit
def gradient_distance(got: dict, want: dict) -> dict:
    """Per watched leaf ``|got - want| / |want|`` (Frobenius norms, float32):
    ``got`` the system's leaves, ``want`` the reference's
    (``reference/sdar.py``'s).  The score bias, whose gradient is zero by
    the architecture, reads ``|got - want|`` over 1 where the reference's is
    zero: 0 where both are, not the 0 / 0 of the other leaves."""
    norm = lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))

    def scale(name):
        size = norm(want[name])
        return (jnp.where(size > 0, size, 1.0) if name.endswith(SCORE_BIAS)
                else size)

    return {name: norm(got[name] - want[name]) / scale(name) for name in want}


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


def changes_agree(distances: dict, tolerance: float = CHANGE_TOLERANCE,
                  router_tolerance: float = ROUTER_CHANGE_TOLERANCE) -> bool:
    """Whether every leaf's change over the replayed updates is within its
    limit of the reference's (and there is one, and all finite: a leaf the
    reference did not move at all has no distance): a router's within
    ``router_tolerance`` (at least ``tolerance``), every other leaf within
    ``tolerance``; the leaves of ``CHANGE_SKIPPED`` are not held."""
    held = {name: d for name, d in distances.items()
            if not name.endswith(CHANGE_SKIPPED)}
    return gradients_agree(held, tolerance, max(tolerance, router_tolerance))
