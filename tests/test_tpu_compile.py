"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Interpret mode (every other kernel test) cannot see what the chip's
compiler refuses: a bool select Mosaic cannot narrow, a scalar read at a
loop index from a VMEM vector, a block that overruns scoped VMEM. These
tests hand the kernels to the TPU compiler for a described v5e that is not
attached (`jax.experimental.topologies`): shapes in, compiled program out,
nothing runs. The topology is described inside a fixture, never while a
module is imported, and this is the only file that describes it.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import api
from repro.actors import samplers as SMP
from repro.core import diffusion as DF
from repro.core import env as EV
from repro.core import rollout as RO
from repro.core import scenarios as SC
from repro.faults import FaultSpec
from repro.kernels.denoiser.kernel import denoiser_chain, denoiser_step
from repro.kernels.env_step.kernel import env_step_pallas

F32, I32 = jnp.float32, jnp.int32
HIDDEN, T_DIM = 256, 16          # paper Table VII denoiser widths


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent compile cache
    off: a compile for a described chip is written to the cache but cannot
    be read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _sds(sharding, shape, dtype=F32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled, name):
    """A Mosaic kernel is there, under its stable HLO instruction name
    (the `pallas_call`'s `name=`), which a profile's op names carry."""
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert f"%{name}." in text or f"%{name} " in text


@pytest.mark.parametrize("faults", [False, True])
@pytest.mark.parametrize("B", [1, 130, 256, 1024])
@pytest.mark.parametrize("E", [8, 32])
def test_env_step_compiles(one_chip, E, B, faults):
    """The state, queue and constants batch-first, transposed inside the
    kernel; the action lane-major. B=1 one narrow block, B=130 two
    padded blocks of 128, B=256 the benchmark's, B=1024 eight steps (with
    the fault columns at E=32 too)."""
    cfg = EV.EnvConfig(num_servers=E)
    K, l, A = cfg.max_tasks, cfg.queue_window, cfg.action_dim
    s = lambda *dims, dt=F32: _sds(one_chip, dims + (B,), dt)  # noqa: E731
    r = lambda n, dt=F32: _sds(one_chip, (B, n), dt)  # noqa: E731
    args = [s(1), r(E), r(E, I32), r(E, I32), r(E, I32),
            r(K, I32), r(K), r(K), r(K, I32), r(K), r(K, I32),
            s(1, dt=I32),
            r(K), r(K, I32), r(K, I32), r(K), r(K), r(K), r(K),
            s(A), r(l, I32), r(l, I32), r(K, I32), s(1, dt=I32)]
    F = FaultSpec().max_down_events
    kw = (dict(fds=s(E, F), fde=s(E, F), fslow=s(E), fcold=s(1))
          if faults else {})
    step = jax.jit(lambda *a, **k: env_step_pallas(cfg, *a, **k,
                                                   interpret=False))
    _assert_kernel(step.lower(*args, **kw).compile(), "env_step_pallas")


@pytest.mark.parametrize("B", [1, 64])
@pytest.mark.parametrize("sampler", ["ddpm", "ddim:5"])
def test_denoiser_chain_compiles(one_chip, sampler, B):
    cfg = EV.EnvConfig()
    A, Fs = cfg.action_dim, cfg.obs_shape[1]
    kind, K = SMP.parse_sampler(sampler)
    sched = DF.vp_schedule(10)
    coefs = (SMP.ddpm_coeffs(sched) if kind == "ddpm"
             else SMP.ddim_coeffs(sched, K))[:3]
    K = coefs[0].shape[0]
    s = lambda *shape: _sds(one_chip, shape)  # noqa: E731
    D = A + T_DIM + Fs
    chain = jax.jit(lambda *a: denoiser_chain(*a, interpret=False))
    compiled = chain.lower(
        s(B, A), s(K, B, A), s(B, Fs), s(K, T_DIM), s(K), s(K), s(K),
        s(D, HIDDEN), s(HIDDEN), s(HIDDEN, HIDDEN), s(HIDDEN),
        s(HIDDEN, A), s(A)).compile()
    _assert_kernel(compiled, "denoiser_chain")


def test_denoiser_step_compiles(one_chip):
    cfg = EV.EnvConfig()
    A = cfg.action_dim
    D = A + T_DIM + cfg.obs_shape[1]
    s = lambda *shape: _sds(one_chip, shape)  # noqa: E731
    step = jax.jit(lambda *a: denoiser_step(*a, interpret=False))
    _assert_kernel(step.lower(
        s(64, D), s(D, HIDDEN), s(HIDDEN), s(HIDDEN, HIDDEN), s(HIDDEN),
        s(HIDDEN, A), s(A)).compile(), "denoiser_step")


def test_fused_rollout_compiles_with_both_kernels(one_chip, monkeypatch):
    """The fused engine's scan as the chip runs it: the env-step kernel and
    the ddim actor's chain kernel, vmapped over B=256 streams inside the
    policy (`actors.program.ActorProgram.vmapped`). The platform checks
    in the kernel wrappers see this host's CPU, so the test tells them
    they are on a TPU. The policy's and the env step's ops carry their
    named scopes in the compiled program's metadata."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    sc = SC.paper_scenarios()[1]            # the paper's E=8 cluster
    ecfg, B = sc.ecfg, 256
    with pytest.warns(api.UntrainedPolicyWarning):
        rp = api.resolve(api.PolicySpec("eat", sampler="ddim:5"), ecfg)
    on = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda x: _sds(one_chip, x.shape, x.dtype), t)
    key = jax.random.PRNGKey(0)
    traces = jax.eval_shape(
        lambda k: SC.make_scenario_trace_batch(k, sc, B), key)
    keys = jax.eval_shape(lambda k: jax.random.split(k, B), key)
    run = jax.jit(lambda tr, p, k: RO.batch_rollout(
        ecfg, tr, rp.policy, p, k, num_steps=4))
    compiled = run.lower(on(traces), on(jax.eval_shape(lambda: rp.params)),
                         on(keys)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 2
    assert "%env_step_pallas" in text and "%denoiser_chain" in text
    assert "/vmap(policy)/" in text and "/env_step/" in text
