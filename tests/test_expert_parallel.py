"""Expert-parallel (MoE) tests: routing algebra, EP vs dense parity over the
all_to_all path, capacity-drop semantics, gradient flow."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map

from distributed_tensorflow_guide_tpu.core.mesh import MeshSpec, build_mesh
from distributed_tensorflow_guide_tpu.parallel.expert import (
    ExpertParallel,
    MoEConfig,
    _topk_dispatch,
    init_moe_params,
    moe_ffn,
)


def dense_moe_reference(params, x, cfg: MoEConfig, capacity: int):
    """Straight-line single-device reference: same routing math, explicit
    per-expert loop, no collectives."""
    logits = x.astype(jnp.float32) @ params["router"].astype(jnp.float32)
    gates = jax.nn.softmax(logits, axis=-1)
    dispatch, combine = _topk_dispatch(gates, cfg.top_k, capacity)
    expert_in = jnp.einsum("tec,td->ecd", dispatch, x)        # (E, C, d)
    h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", expert_in, params["w_in"]))
    out = jnp.einsum("ecf,efd->ecd", h, params["w_out"])
    return jnp.einsum("tec,ecd->td", combine, out)


# -- routing algebra ---------------------------------------------------------


def test_topk_dispatch_basic():
    # 4 tokens, 2 experts, plenty of capacity
    gates = jnp.array([[0.9, 0.1], [0.2, 0.8], [0.7, 0.3], [0.4, 0.6]])
    dispatch, combine = _topk_dispatch(gates, top_k=1, capacity=4)
    # each token lands exactly once, in its argmax expert
    assert np.allclose(dispatch.sum(axis=(1, 2)), 1.0)
    chosen = np.argmax(np.asarray(dispatch.sum(axis=2)), axis=1)
    assert list(chosen) == [0, 1, 0, 1]
    # combine weight equals the winning gate
    got = np.asarray(combine.sum(axis=(1, 2)))
    assert np.allclose(got, [0.9, 0.8, 0.7, 0.6], atol=1e-6)
    # slot positions within an expert are distinct
    e0 = np.asarray(dispatch[:, 0, :])  # tokens 0 and 2 -> slots 0 and 1
    assert e0[0, 0] == 1 and e0[2, 1] == 1


def test_topk_dispatch_top2_uses_two_experts():
    gates = jnp.array([[0.6, 0.3, 0.1]])
    dispatch, combine = _topk_dispatch(gates, top_k=2, capacity=2)
    chosen = np.flatnonzero(np.asarray(dispatch.sum(axis=2))[0])
    assert list(chosen) == [0, 1]
    assert np.allclose(np.asarray(combine[0].sum(1))[:2], [0.6, 0.3],
                       atol=1e-6)


def test_topk_dispatch_capacity_drops_overflow():
    # all 4 tokens want expert 0 but capacity is 2 -> 2 dropped
    gates = jnp.array([[0.99, 0.01]] * 4)
    dispatch, _ = _topk_dispatch(gates, top_k=1, capacity=2)
    assert float(dispatch.sum()) == 2.0
    # first two tokens (routing is order-deterministic) kept
    assert np.allclose(np.asarray(dispatch.sum(axis=(1, 2))), [1, 1, 0, 0])


# -- EP path parity ----------------------------------------------------------


@pytest.mark.parametrize("top_k", [1, 2])
def test_ep_matches_dense_reference(top_k):
    cfg = MoEConfig(d_model=8, d_ff=16, num_experts=4, top_k=top_k,
                    capacity_factor=2.0)
    mesh = build_mesh(MeshSpec(data=2, expert=4))
    ep = ExpertParallel(mesh, cfg)
    params = init_moe_params(cfg, jax.random.PRNGKey(0))
    t_global = 64
    x = jax.random.normal(jax.random.PRNGKey(1), (t_global, cfg.d_model))

    y, aux = ep.apply(ep.shard_params(params), x)

    # dense reference with matching per-shard capacity: the sharded version
    # routes each 8-token shard independently (t_local = 64/8 devices = 8)
    t_local = t_global // (2 * 4)
    capacity = max(1, int(np.ceil(
        cfg.top_k * t_local * cfg.capacity_factor / cfg.num_experts)))
    y_ref = jnp.concatenate([
        dense_moe_reference(params, x[i * t_local:(i + 1) * t_local], cfg,
                            capacity)
        for i in range(2 * 4)
    ])
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=2e-4, atol=2e-5)
    assert np.isfinite(float(aux["load_balance"]))
    # ~1 at balanced routing (exactly >= 1 only for top_k=1 with no drops)
    assert float(aux["load_balance"]) > 0.9
    # aux z_loss must be the GLOBAL statistic (reduced over data AND expert
    # axes), equal to computing it over the full token set on one device
    logits = x @ params["router"]
    z_ref = float(jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2))
    np.testing.assert_allclose(float(aux["z_loss"]), z_ref, rtol=1e-5)


def test_ep_train_step_learns_and_balances():
    cfg = MoEConfig(d_model=8, d_ff=32, num_experts=8, top_k=2,
                    capacity_factor=2.0)
    mesh = build_mesh(MeshSpec(data=1, expert=8))
    ep = ExpertParallel(mesh, cfg)
    params = ep.shard_params(init_moe_params(cfg, jax.random.PRNGKey(0)))
    step = ep.make_train_step(lr=0.05)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(128, cfg.d_model), jnp.float32)
    y = jnp.asarray(np.tanh(rng.randn(128, cfg.d_model)), jnp.float32)
    losses = []
    for _ in range(20):
        params, metrics = step(params, x, y)
        losses.append(float(metrics["loss"]))
    assert all(np.isfinite(losses))
    # 20 steps (was 15): the descent rate depends on the PRNG-seeded init,
    # whose bits differ across jax threefry configs; the contract is
    # "learns", not a specific per-step rate
    assert losses[-1] < losses[0] * 0.9, losses


def test_ep_validates_divisibility():
    mesh = build_mesh(MeshSpec(data=2, expert=4))
    with pytest.raises(ValueError, match="divisible"):
        ExpertParallel(mesh, MoEConfig(d_model=4, d_ff=8, num_experts=6))


def test_moe_ffn_rejects_wrong_local_expert_count():
    cfg = MoEConfig(d_model=4, d_ff=8, num_experts=4)
    params = init_moe_params(cfg, jax.random.PRNGKey(0))  # full stacks

    def run(x):
        return moe_ffn(params, x, cfg)[0]  # unsplit params: E_local==E_global

    mesh = build_mesh(MeshSpec(data=1, expert=4), devices=jax.devices()[:4])
    from jax.sharding import PartitionSpec as P

    with pytest.raises(ValueError, match="local"):
        shard_map(run, mesh=mesh, in_specs=(P("expert"),),
                      out_specs=P("expert"), check_vma=False)(
            jnp.zeros((16, 4)))
