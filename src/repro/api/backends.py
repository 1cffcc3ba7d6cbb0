"""Pluggable execution backends for the batched rollout engine.

Every backend is one callable with the `batch_rollout` calling convention:

    fn(ecfg, traces, policy, params, keys, *,
       num_steps=None, collect=False, init_state=None) -> RolloutResult

so episodic evaluation, streaming windows (`run_stream(rollout_fn=...)`)
and training collection all swap engines through one seam:

* ``reference`` — the legacy vmap-of-scans engine on the compositional
  `env.step` (`batch_rollout(fused=False)`); the bitwise oracle.
* ``fused`` — the fused env-step op engine (`batch_rollout(fused=True)`,
  the repo default since PR 3).
* ``sharded`` — the fused program `shard_map`'d over a 1-D device mesh
  (`launch.mesh.make_data_mesh`): the batch/stream axis splits across
  devices, policy params are replicated, every output leaf comes back
  sharded on its leading axis. Each shard runs the *same* per-row program
  as the fused backend (the env's FMA/reciprocal bitwise armor makes the
  per-row math independent of the local batch size), so results are
  bitwise-identical to ``fused`` — CI asserts this under
  XLA_FLAGS=--xla_force_host_platform_device_count=8.
* ``serving`` — the real serving cluster (`repro.serving.backend`): one
  physical pool (batch must be 1) whose scheduler state is a mirror
  `EnvState` advanced by the shared decision step, with real weight loads
  and patch-parallel prefill/decode per scheduled task. Virtual time is
  bitwise-identical to ``fused``; wall-clock mode patches measured
  latencies back into rewards and observations. The returned callable is
  STATEFUL (the pool persists across calls — that is the point); build one
  per consumer via `rollout_fn_for` and `reset()` it between runs.

Compiled sharded programs are cached per (ecfg, policy, step budget, mesh)
— the streaming engine reuses one program across all its windows.
"""
from __future__ import annotations

import functools
import math
import warnings
from typing import Optional

import jax
from jax.sharding import PartitionSpec as P

from repro.api.specs import BACKENDS, ExecSpec
from repro.core import env as EV
from repro.core import rollout as RO
from repro.launch import mesh as MX


def device_count() -> int:
    """Local devices visible to the sharded backend."""
    return jax.local_device_count()


def resolve_shards(batch: int, spec: ExecSpec) -> int:
    """Mesh size the sharded backend will actually use for a batch: the
    requested device count (0 = all local), degraded to gcd(batch, devices)
    when the batch axis does not divide evenly."""
    want = spec.mesh_devices or device_count()
    if want > device_count():
        raise ValueError(
            f"ExecSpec.mesh_devices={spec.mesh_devices} but only "
            f"{device_count()} local devices exist (on CPU, force more with "
            "XLA_FLAGS=--xla_force_host_platform_device_count=N)")
    return math.gcd(int(batch), want)


@functools.lru_cache(maxsize=None)
def _sharded_program(ecfg: EV.EnvConfig, policy, num_steps: Optional[int],
                     collect: bool, fused_impl: str, ndev: int, axis: str,
                     has_init: bool):
    """jit(shard_map(batch_rollout)) over a 1-D `axis` mesh of `ndev`
    devices. traces/keys (and the carried init_state, when given) shard on
    their leading (batch) axis, params replicate, every result leaf comes
    back batch-sharded. Without a carried state the fresh reset is traced
    *inside* the program (each shard resets its local batch), matching the
    fused path's behaviour instead of materialising a host-side reset."""
    mesh = MX.make_data_mesh(ndev, axis=axis)

    def run(traces, params, keys, *init_state):
        return RO.batch_rollout(ecfg, traces, policy, params, keys,
                                num_steps=num_steps, collect=collect,
                                init_state=init_state[0] if has_init else None,
                                fused=True, fused_impl=fused_impl)

    in_specs = (P(axis), P(), P(axis)) + ((P(axis),) if has_init else ())
    f = jax.shard_map(run, mesh=mesh, in_specs=in_specs,
                      out_specs=P(axis), check_vma=False)
    return jax.jit(f)


def rollout_fn_for(spec: ExecSpec = ExecSpec()):
    """Resolve an ExecSpec to a rollout callable (batch_rollout convention).

    The returned callable is safe to reuse across calls and batch sizes;
    program compilation is cached underneath (by `batch_rollout`'s jit for
    reference/fused, by `_sharded_program` for sharded).
    """
    if spec.backend not in BACKENDS:
        raise ValueError(
            f"backend must be one of {BACKENDS}, got {spec.backend!r}")

    if spec.backend == "serving":
        # lazy: the serving stack (model zoo, executor) is heavy and only
        # needed when actually serving. Fresh state per resolution — each
        # consumer owns its own pool, persistent across its windows/rounds.
        from repro.serving.backend import serving_rollout
        return serving_rollout(spec)

    if spec.backend in ("reference", "fused"):
        fused = spec.backend == "fused"

        def fn(ecfg, traces, policy, params, keys, *, num_steps=None,
               collect=False, init_state=None):
            return RO.batch_rollout(ecfg, traces, policy, params, keys,
                                    num_steps=num_steps, collect=collect,
                                    init_state=init_state, fused=fused,
                                    fused_impl=spec.fused_impl)
        fn.backend = spec.backend
        return fn

    def fn(ecfg, traces, policy, params, keys, *, num_steps=None,
           collect=False, init_state=None):
        B = keys.shape[0]
        ndev = resolve_shards(B, spec)
        want = spec.mesh_devices or device_count()
        if ndev < want:
            warnings.warn(
                f"sharded backend: batch {B} does not divide over {want} "
                f"devices; degrading to a {ndev}-device mesh", stacklevel=2)
        prog = _sharded_program(ecfg, policy, num_steps, collect,
                                spec.fused_impl, ndev, spec.mesh_axis,
                                init_state is not None)
        args = (traces, params, keys) + (
            (init_state,) if init_state is not None else ())
        return prog(*args)
    fn.backend = "sharded"
    return fn
