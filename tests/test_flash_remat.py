"""Every remat policy of ``models/`` keeps the flash kernel's output and
log-sum-exp (``ops/flash_attention.py`` ``FLASH_RESIDUALS``), so that the
backward pass of a rematerialised layer reads them and does not run the
forward kernel a second time; and keeping them changes no number. On the
CPU, kernels in interpret mode, tiny widths."""

import dataclasses
import signal

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.ad_checkpoint import checkpoint_name

from dlrover_tpu.models import llama, looped, moe
from dlrover_tpu.ops.flash_attention import FLASH_RESIDUALS

NAMES = FLASH_RESIDUALS + moe._SAVED
SEQ = 32


@pytest.fixture(autouse=True)
def time_limit():
    """Each test's own limit, well inside the suite's."""
    def late(signum, frame):
        raise TimeoutError("the test took over 120 s")

    before = signal.signal(signal.SIGALRM, late)
    signal.alarm(120)
    yield
    signal.alarm(0)
    signal.signal(signal.SIGALRM, before)


def saved_names(policy):
    """Which of ``NAMES`` ``policy`` lets ``jax.checkpoint`` keep: the
    policy asked about the equation ``checkpoint_name`` makes, as
    ``jax.checkpoint`` asks it."""
    eqn = jax.make_jaxpr(lambda x: checkpoint_name(x, NAMES[0]))(
        jnp.ones((8,))).eqns[0]
    avals = [v.aval for v in eqn.invars]
    return {name for name in NAMES
            if policy(eqn.primitive, *avals, **dict(eqn.params, name=name))}


@pytest.mark.parametrize("policy,saved", [
    pytest.param(None, FLASH_RESIDUALS, id="None"),
    pytest.param("dots", FLASH_RESIDUALS + moe._SAVED, id="dots"),
])
def test_the_expert_policy_keeps_the_flash_residuals(policy, saved):
    """The experts' projections are kept only where dots are: under
    ``None`` the policy keeps what only the kernel can make and nothing
    else. (That llama's and the looped model's policies keep the two
    names shows in the traced steps below.)"""
    config = moe.MoEConfig(remat_policy=policy)
    assert saved_names(moe._remat_policy(config)) == set(saved)


def test_an_unknown_policy_is_refused():
    with pytest.raises(ValueError, match="unknown remat_policy"):
        llama._remat_policy(llama.LlamaConfig(remat_policy="everything"))


def kernel_calls(jaxpr):
    """Names of the Pallas calls in ``jaxpr`` and in every jaxpr inside it
    (a scan's body counts once, however often it runs)."""
    names = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            name = eqn.params["name"]
            names.append(str(getattr(name, "name", name)))
            continue
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    names += kernel_calls(sub)
    return names


FAMILIES = {
    "llama": (llama.LlamaConfig.tiny, llama.init_params,
              llama.next_token_loss),
    "looped": (lambda: dataclasses.replace(looped.LoopedConfig.tiny(),
                                           n_passes=2),
               looped.init_params, looped.next_token_loss),
    "moe": (moe.MoEConfig.tiny, moe.init_params, moe.next_token_loss),
}


def step(family, policy):
    """(loss, gradients, Pallas calls of the traced step) of one tiny
    step with every layer under ``jax.checkpoint``."""
    make_config, init_params, loss_fn = FAMILIES[family]
    config = dataclasses.replace(
        make_config(), remat=True, remat_policy=policy,
        use_flash_attention=True, dtype=jnp.bfloat16)
    params = init_params(config, jax.random.PRNGKey(7))
    tokens = jnp.asarray(np.random.default_rng(7).integers(
        0, config.vocab_size, size=(2, SEQ + 1), dtype=np.int32))
    fn = jax.value_and_grad(lambda p, t: loss_fn(p, t, config))
    calls = kernel_calls(jax.make_jaxpr(fn)(params, tokens).jaxpr)
    loss, grads = jax.jit(fn)(params, tokens)
    return loss, grads, calls


@pytest.mark.parametrize("policy", [None, "dots"])
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_kept_residuals_change_no_number(family, policy, monkeypatch):
    """The step with the flash residuals kept against the same step with
    their names taken out of the policy: the forward kernel is traced once
    where it was traced twice, the loss is the same to the last bit, and
    so is every gradient (to a millionth, where the two programs fuse
    their f32 arithmetic apart)."""
    loss, grads, calls = step(family, policy)
    monkeypatch.setattr(llama, "FLASH_RESIDUALS", ())
    # moe's loss jits its forward with the config static: trace it anew
    jax.clear_caches()
    loss_replayed, grads_replayed, calls_replayed = step(family, policy)

    assert calls.count("flash_fwd") == 1, calls
    assert calls_replayed.count("flash_fwd") == 2, calls_replayed
    for kernel in ("flash_bwd_dq", "flash_bwd_dkv"):
        assert calls.count(kernel) == calls_replayed.count(kernel) == 1
    assert np.asarray(loss).tobytes() == np.asarray(loss_replayed).tobytes()
    for got, want in zip(jax.tree.leaves(grads),
                         jax.tree.leaves(grads_replayed)):
        got, want = (np.asarray(g, np.float32) for g in (got, want))
        assert np.allclose(got, want, rtol=1e-6, atol=0), (
            np.abs(got - want).max())


def sp_step(strategy, policy):
    """(loss, gradients, Pallas calls of the traced step) of
    sequence-parallel attention over four CPU devices, the flash kernel its
    inner block, under ``jax.checkpoint`` with the llama policy."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from dlrover_tpu.parallel.ring_attention import ring_attention
    from dlrover_tpu.parallel.ulysses import ulysses_attention

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(1, 1, 1, 4),
                ("dp", "fsdp", "tp", "sp"))
    attend = {"ring": ring_attention, "ulysses": ulysses_attention}[strategy]
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    sharded = NamedSharding(mesh, P(("dp", "fsdp"), "tp", "sp", None))
    qkv = [jax.device_put(
        jax.random.normal(key, (1, 4, SEQ, 16), jnp.bfloat16), sharded)
        for key in keys]
    config = llama.LlamaConfig(remat_policy=policy)

    def loss(q, k, v):
        o = attend(q, k, v, mesh, use_pallas=True, block_q=8, block_k=8)
        return (o.astype(jnp.float32) ** 2).sum()

    fn = jax.value_and_grad(
        jax.checkpoint(loss, policy=llama._remat_policy(config)),
        argnums=(0, 1, 2))
    calls = kernel_calls(jax.make_jaxpr(fn)(*qkv).jaxpr)
    return (*jax.jit(fn)(*qkv), calls)


@pytest.mark.skipif(len(jax.devices()) < 4, reason="needs 4 cpu devices")
@pytest.mark.parametrize("policy", [None, "dots"])
@pytest.mark.parametrize("strategy", ["ring", "ulysses"])
def test_sequence_parallel_attention_keeps_its_numbers(
        strategy, policy, monkeypatch):
    """Ring and Ulysses attention call the same kernel, so under a remat
    policy they keep each call's output and log-sum-exp too (one more
    (B, H, S, D) and (B, H, S) a ring step): the forward kernel is traced
    half as often, the loss is the same to the last bit, and the gradients
    to a millionth, as with the names taken out of the policy."""
    loss, grads, calls = sp_step(strategy, policy)
    monkeypatch.setattr(llama, "FLASH_RESIDUALS", ())
    jax.clear_caches()
    loss_replayed, grads_replayed, calls_replayed = sp_step(strategy, policy)

    assert calls_replayed.count("flash_fwd") == 2 * calls.count("flash_fwd")
    assert np.asarray(loss).tobytes() == np.asarray(loss_replayed).tobytes()
    for got, want in zip(grads, grads_replayed):
        got, want = (np.asarray(g, np.float32) for g in (got, want))
        assert np.allclose(got, want, rtol=1e-6, atol=0), (
            np.abs(got - want).max())
