"""shard_map MoE == pjit MoE in the no-drop regime (8 host devices)."""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs.base import MoEConfig  # noqa: E402
from repro.launch.mesh import auto_mesh  # noqa: E402
from repro.models.transformer.model import _act  # noqa: E402
from repro.models.transformer.moe import init_moe_params, moe_ffn  # noqa: E402
from repro.models.transformer.moe_sharded import moe_ffn_sharded  # noqa: E402


def main():
    assert len(jax.devices()) == 8
    mesh = auto_mesh((4, 2), ("data", "model"))
    cfg = MoEConfig(n_experts=8, top_k=2, d_ff=32,
                    capacity_factor=64.0,  # no-drop regime
                    router_aux_weight=0.0)  # aux estimators differ by a
    # cross-shard covariance term (checked separately with loose tol below)
    d = 16
    t = 256
    params = init_moe_params(jax.random.PRNGKey(0), d, cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (t, d), jnp.float32)

    y_ref, aux_ref = jax.jit(
        lambda p, x: moe_ffn(p, x, cfg, _act("silu")))(params, x)

    with mesh:
        y_sm, aux_sm = jax.jit(
            lambda p, x: moe_ffn_sharded(p, x, cfg, _act("silu"), mesh=mesh,
                                         dp_axes=("data",),
                                         tp_axis="model"))(params, x)
    np.testing.assert_allclose(np.asarray(y_sm), np.asarray(y_ref),
                               rtol=2e-4, atol=2e-5)
    # aux (computed with weight 1.0) is pmean of per-shard sum(f_e*p_e):
    # differs from the global product-of-means by a cross-shard covariance
    # (the standard distributed load-balance estimator) -> loose tolerance
    cfg_aux = MoEConfig(n_experts=8, top_k=2, d_ff=32, capacity_factor=64.0)
    _, a_ref = jax.jit(lambda p, x: moe_ffn(p, x, cfg_aux,
                                            _act("silu")))(params, x)
    with mesh:
        _, a_sm = jax.jit(lambda p, x: moe_ffn_sharded(
            p, x, cfg_aux, _act("silu"), mesh=mesh, dp_axes=("data",),
            tp_axis="model"))(params, x)
    np.testing.assert_allclose(float(a_sm), float(a_ref), rtol=8e-2)

    # gradients agree too (the a2a transpose path)
    def loss_ref(p):
        y, aux = moe_ffn(p, x, cfg, _act("silu"))
        return (y * y).mean() + aux

    def loss_sm(p):
        with mesh:
            y, aux = moe_ffn_sharded(p, x, cfg, _act("silu"), mesh=mesh,
                                     dp_axes=("data",), tp_axis="model")
        return (y * y).mean() + aux

    g_ref = jax.grad(loss_ref)(params)
    g_sm = jax.jit(jax.grad(loss_sm))(params)
    for k in ("w1", "w2", "w3", "router"):
        np.testing.assert_allclose(np.asarray(g_sm[k]), np.asarray(g_ref[k]),
                                   rtol=5e-3, atol=1e-5)
    print("MOE_SHARDED_OK")


if __name__ == "__main__":
    main()
