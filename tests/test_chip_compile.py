"""Compile the policy's main-path programs for a TPU v5e that is described,
not attached: the fused tree-CNN kernel and its gradient, the batched act
step and the PPO update, with and without the fused kernel, at the
agent's full width. Nothing runs; the compiler refuses what the chip's
compiler would refuse (tiling, VMEM, memory), and the fused programs must
contain the Mosaic kernel (`tpu_custom_call`).

The topology is described inside a fixture, never at import: one process
at a time may load the TPU's library, and every test worker imports this
file.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import nets
from repro.core.agent import AgentConfig, AqoraAgent
from repro.core.encoding import WorkloadMeta
from repro.kernels.tree_conv import tree_cnn_fused
from repro.sql import workloads

F, H = 26, 96          # JOB-like feature width; AgentConfig().hidden


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def on_tpu(monkeypatch):
    """Code that asks `jax.default_backend()` takes its TPU branch (Mosaic
    kernels, not the interpreter) while this process compiles for one."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _spec(x, sharding):
    return jax.ShapeDtypeStruct(np.shape(x), jnp.result_type(x),
                                sharding=sharding)


def _specs(tree, sharding):
    return jax.tree_util.tree_map(lambda x: _spec(x, sharding), tree)


def _dots_at_full_f32(lowered) -> bool:
    """Every XLA matmul of the program is pinned to full f32, which the
    TPU would otherwise run as one bf16 pass."""
    dots = [l for l in lowered.as_text().splitlines() if "dot_general" in l]
    return bool(dots) and all("HIGHEST, HIGHEST" in l for l in dots)


def _state_specs(B, N, sharding):
    return (jax.ShapeDtypeStruct((B, N, F), jnp.float32, sharding=sharding),
            jax.ShapeDtypeStruct((B, N), jnp.int32, sharding=sharding),
            jax.ShapeDtypeStruct((B, N), jnp.int32, sharding=sharding),
            jax.ShapeDtypeStruct((B, N), jnp.float32, sharding=sharding))


@pytest.fixture(scope="module")
def meta():
    wl = workloads.make_workload("job", n_train=100, n_test_per_template=1)
    m = WorkloadMeta.from_workload(wl)
    assert m.feat_dim == F
    return m


@pytest.fixture(scope="module", params=[False, True], ids=["xla", "fused"])
def agent(request, meta):
    return AqoraAgent(meta, dataclasses.replace(
        AgentConfig(), fused_treecnn=request.param), seed=0)


@pytest.mark.parametrize("B,N", [(8, 64), (8, 48)])
def test_tree_cnn_fused_forward_compiles(one_chip, B, N):
    params = jax.eval_shape(lambda: nets.init_encoder(
        jax.random.PRNGKey(0), "treecnn", F, H))
    fwd = jax.jit(lambda f, l, r, m, p: tree_cnn_fused(
        f, l, r, m, p, interpret=False))
    text = fwd.lower(*_state_specs(B, N, one_chip),
                     _specs(params, one_chip)).compile().as_text()
    assert "tpu_custom_call" in text


def test_tree_cnn_fused_grad_compiles(one_chip):
    params = jax.eval_shape(lambda: nets.init_encoder(
        jax.random.PRNGKey(0), "treecnn", F, H))

    def loss(p, f, l, r, m):
        return tree_cnn_fused(f, l, r, m, p, interpret=False).sum()

    # the backward rematerializes through the jnp reference, so only the
    # loss value keeps the forward kernel in the program
    feat, left, right, mask = _state_specs(8, 48, one_chip)
    text = jax.jit(jax.value_and_grad(loss)).lower(
        _specs(params, one_chip), feat, left, right,
        mask).compile().as_text()
    assert "tpu_custom_call" in text


def test_act_batch_compiles(one_chip, on_tpu, agent):
    B, N = 8, agent._nodes
    amask = jax.ShapeDtypeStruct((B, agent.space.d), jnp.float32,
                                 sharding=one_chip)
    keys = jax.ShapeDtypeStruct((B, 2), jnp.uint32, sharding=one_chip)
    lowered = agent._act_batch_jit.lower(
        _specs(agent.actor, one_chip), *_state_specs(B, N, one_chip),
        amask, keys, explore=True)
    assert _dots_at_full_f32(lowered)
    text = lowered.compile().as_text()
    assert ("tpu_custom_call" in text) == agent.cfg.fused_treecnn


def test_update_epochs_compiles(one_chip, on_tpu, agent):
    B, K, N, d = 8, agent.cfg.max_steps + 1, agent._nodes, agent.space.d
    T = B * (K - 1)
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
    batch = {"feat": f32(T, N, F), "left": i32(T, N), "right": i32(T, N),
             "mask": f32(T, N), "amask": f32(T, d), "action": i32(T),
             "old_logp": f32(T), "q": f32(T), "valid": f32(T)}
    sbatch = {"feat": f32(B * K, N, F), "left": i32(B * K, N),
              "right": i32(B * K, N), "mask": f32(B * K, N),
              "v_target": f32(B * K), "valid": f32(B * K)}
    lowered = agent._update_epochs.lower(
        *(_specs(t, one_chip) for t in (agent.actor, agent.critic,
                                        agent.aopt, agent.copt)),
        batch, sbatch)
    assert _dots_at_full_f32(lowered)
    compiled = lowered.compile()
    assert ("tpu_custom_call" in compiled.as_text()) == \
        agent.cfg.fused_treecnn
    assert compiled.memory_analysis() is not None
