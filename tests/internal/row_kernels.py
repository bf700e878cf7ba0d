"""The expert layer's row kernels (``bagua_tpu/ops/moe_rows.py``) on the CPU:
the gates say yes and every ``pallas_call`` runs in interpret mode — steered
in the test, not by an option of the program — and one layer's output and
gradients on that path beside the fallback's."""

import jax
import jax.numpy as jnp
import pytest

import bagua_tpu.ops.gmm as gmm_mod
import bagua_tpu.ops.moe_rows as rows_mod
from bagua_tpu.telemetry import counters

#: what one layer's two paths are compared in: its output, and the
#: gradients of its input, its gates (through the router) and its leaves
QUANTITIES = ["out", "d_x", "router", "expert_wi", "expert_wo", "expert_wg"]


def force_row_kernels(patch):
    """The grouped-matmul layout block-aligned, the row kernels' gates open
    where the shapes allow, every kernel interpreted."""
    real = gmm_mod.pl.pallas_call
    patch.setattr(gmm_mod, "_use_kernel", lambda *a: True)
    patch.setattr(rows_mod, "_on_tpu", lambda: True)
    patch.setattr(gmm_mod.pl, "pallas_call",
                  lambda *a, **kw: real(*a, **{**kw, "interpret": True}))


def both_paths(layer, tokens=128, d=128, seed=0):
    """``(by the kernels, by the fallback, sites on each)``: a layer's
    :data:`QUANTITIES` over ``tokens`` rows of width ``d``, computed with
    the row kernels forced and with nothing forced, and what the gauge
    ``moe/row_kernel_sites`` read after each."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(keys[0], (2, tokens // 2, d))
    g = jax.random.normal(keys[1], (2, tokens // 2, d))
    params = layer.init(keys[2], x)["params"]

    def loss(params, x):
        out = layer.apply({"params": params}, x)
        return jnp.sum(out * g), out

    def quantities():
        (_, out), (d_params, d_x) = jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True)(params, x)
        found = {"out": out, "d_x": d_x,
                 "router": d_params["router"]["kernel"],
                 **{name: d_params[name] for name in d_params
                    if name.startswith("expert_")}}
        return found, counters.get("moe/row_kernel_sites")

    fallback, sites_fallback = quantities()
    with pytest.MonkeyPatch.context() as patch:
        force_row_kernels(patch)
        forced, sites_forced = quantities()
    return forced, fallback, (sites_forced, sites_fallback)


def assert_the_same_layer(forced, fallback, sites, quantity):
    """The kernels' layer is the fallback's: three of the four row
    movements took a kernel (the dispatch itself stays XLA's gather), none
    on the fallback."""
    import numpy as np

    assert sites == (3, 0)
    assert set(forced) == set(fallback)
    if quantity not in fallback:
        assert quantity == "expert_wg"          # an ungated layer has none
        return
    got, want = forced[quantity], fallback[quantity]
    scale = float(jnp.abs(want).max())
    assert scale > 0, "a quantity that is zero everywhere tests nothing"
    np.testing.assert_allclose(got, want, atol=2e-5 * scale, rtol=0)
