"""ActorProgram: the one compiled-inference owner per (env, policy).

Before this layer, every consumer of a rollout-protocol policy re-derived
and re-cached its own compiled program: the serving backend jitted a
key-split + forward (`_policy_prog`), the fused rollout scan re-built
`jax.vmap(policy, ...)` per trace, the decision-latency probe jitted an
ad-hoc lambda per call, and the registry handed out bare callables.
`actor_program(ecfg, policy)` now owns all of those views:

* ``act(trace, state, obs, key, params)`` — ONE jitted per-decision
  program: split the carried key, run the actor, return
  (key', action, extras). Exactly the serving backend's decision seam; the
  latency probe (`telemetry.profile.profile_policy`) measures this same
  program, so BENCH_decision_latency numbers and serving's decision spans
  describe literally the same XLA executable.
* ``vmapped`` — the batch-axis view `vmap(policy, (None, 0, 0, 0, 0))`
  the fused rollout scan consumes.
* ``policy`` — the raw protocol callable (a static jit argument: identity
  IS the compiled-program cache key, which is why programs are cached per
  (ecfg, policy) and policies come from lru-cached factories).
* ``sampler`` — the policy's sampler label when it carries one
  (`actors.policies` stamps it), for telemetry span/metric attribution.

Per-shape compilation is jit's own cache: one `ActorProgram` serves every
batch shape its consumers throw at it.
"""
from __future__ import annotations

import functools

import jax


class ActorProgram:
    """Compiled inference views of one rollout-protocol policy on one env.

    Build via `actor_program(ecfg, policy)` — the lru-cached factory is
    what guarantees one program (and one set of compiled executables) per
    (env config, policy callable).
    """

    def __init__(self, ecfg, policy):
        self.ecfg = ecfg
        self.policy = policy
        self.sampler = getattr(policy, "sampler", None)
        self._act = jax.jit(self._split_act)
        self._vmapped = None

    def _split_act(self, trace, state, obs, key, params):
        # the actor runs as the batch-axis view on a batch of one, so this
        # seam and the fused scan at B=1 are one program: compiled
        # unbatched, XLA:CPU contracts the reverse chain's multiply-adds
        # differently and the actions drift by a few ulps
        key, k_act = jax.random.split(key)
        batch = jax.tree_util.tree_map(lambda x: x[None],
                                       (k_act, trace, state, obs))
        out = self.vmapped(params, *batch)
        action, extras = jax.tree_util.tree_map(lambda x: x[0], out)
        return key, action, extras

    def act(self, trace, state, obs, key, params):
        """One decision at the serving seam: split the carried key, run the
        actor. Returns (key', action, extras)."""
        return self._act(trace, state, obs, key, params)

    @property
    def vmapped(self):
        """The fused-scan view: `vmap(policy, (None, 0, 0, 0, 0))` (shared
        params, batched key/trace/state/obs). Its ops carry the named
        scope `policy` in a profile."""
        if self._vmapped is None:
            policy = self.policy

            @functools.wraps(policy)
            def scoped(*args):
                with jax.named_scope("policy"):
                    return policy(*args)

            self._vmapped = jax.vmap(scoped, in_axes=(None, 0, 0, 0, 0))
        return self._vmapped

    def __repr__(self):
        s = f", sampler={self.sampler!r}" if self.sampler else ""
        return (f"ActorProgram({getattr(self.policy, '__name__', 'policy')}"
                f"{s})")


@functools.lru_cache(maxsize=None)
def actor_program(ecfg, policy) -> ActorProgram:
    """The shared compiled-inference layer: one `ActorProgram` per
    (EnvConfig, policy callable), cached for the process lifetime."""
    return ActorProgram(ecfg, policy)
