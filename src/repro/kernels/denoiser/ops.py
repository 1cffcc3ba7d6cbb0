"""Public wrappers: adapt the diffusion-policy params dict to the fused
denoiser kernels.

Two entry points, mirroring `kernels/env_step/ops.py`:

* ``denoise_eps_fused`` — one eps-MLP forward (drop-in for
  `repro.core.diffusion.denoise_eps`), one `denoiser_step` kernel launch.
* ``denoise_chain`` — the whole K-step reverse chain with
  ``impl="auto"|"ref"|"pallas"`` dispatch: "ref" is the jnp oracle/CPU fast
  path (`ref.denoiser_chain_ref`, shape-polymorphic so it vmaps inside the
  fused rollout scan), "pallas" is the single-launch whole-chain kernel
  (`kernel.denoiser_chain`; interpret mode on CPU). "auto" picks pallas on
  gpu/tpu and ref elsewhere. Both are bitwise-identical on the same inputs.

Both validate the params dict shape up front: the kernels hard-code the
paper's 3-layer Mish MLP (Table VII), and a params dict with any other
depth used to be silently mis-read (extra layers ignored / IndexError).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.diffusion import timestep_embedding
from repro.kernels.denoiser import ref as KREF
from repro.kernels.denoiser.kernel import denoiser_chain, denoiser_step


def _flat_weights(denoiser_params):
    """Validate the 3-layer MLP shape and flatten to (w1, b1, ..., b3)."""
    layers = denoiser_params.get("layers") \
        if hasattr(denoiser_params, "get") else None
    if layers is None:
        raise ValueError(
            "denoiser params must be the core.networks.init_mlp dict "
            "{'layers': [{'w','b'}, ...]}; got "
            f"{type(denoiser_params).__name__}")
    if len(layers) != 3:
        raise ValueError(
            f"fused denoiser kernels support exactly 3 MLP layers "
            f"(in -> hidden -> hidden -> out, paper Table VII); got "
            f"{len(layers)} layers — use repro.core.diffusion.denoise_eps "
            "for other depths")
    return (layers[0]["w"], layers[0]["b"], layers[1]["w"], layers[1]["b"],
            layers[2]["w"], layers[2]["b"])


def resolve_impl(impl: str = "auto") -> str:
    if impl == "auto":
        return "pallas" if jax.default_backend() in ("gpu", "tpu") else "ref"
    if impl not in ("ref", "pallas"):
        raise ValueError(f"impl must be auto|ref|pallas, got {impl!r}")
    return impl


def _interpret(interpret) -> bool:
    """Compiled kernels on gpu/tpu, the Pallas interpreter elsewhere."""
    if interpret is None:
        return jax.default_backend() not in ("gpu", "tpu")
    return bool(interpret)


def denoise_eps_fused(denoiser_params, x, i, f_s, t_dim: int = 16,
                      interpret=None):
    """Drop-in for repro.core.diffusion.denoise_eps (batched inputs)."""
    w1, b1, w2, b2, w3, b3 = _flat_weights(denoiser_params)
    temb = timestep_embedding(i, t_dim)
    inp = jnp.concatenate([x, temb, f_s], axis=-1)
    squeeze = inp.ndim == 1
    if squeeze:
        inp = inp[None]
    out = denoiser_step(inp, w1, b1, w2, b2, w3, b3,
                        interpret=_interpret(interpret))
    return out[0] if squeeze else out


def denoise_chain(denoiser_params, x, noises, f_s, tembs, coef_x, coef_e,
                  coef_n, *, impl: str = "auto", block_b: int = 128,
                  interpret=None):
    """Whole K-step reverse chain on the params dict.

    x: (..., A); noises: (K, ..., A); f_s: (..., F); tembs: (K, t_dim);
    coef_*: (K,). Returns tanh(x_0) with x's shape. The pallas path
    requires a 2-D batch (1-D inputs are expanded and squeezed back).
    """
    w = _flat_weights(denoiser_params)
    impl = resolve_impl(impl)
    if impl == "ref":
        return KREF.denoiser_chain_ref(x, noises, f_s, tembs,
                                       coef_x, coef_e, coef_n, *w)
    squeeze = x.ndim == 1
    if squeeze:
        x, noises, f_s = x[None], noises[:, None], f_s[None]
    out = denoiser_chain(x, noises, f_s, tembs, coef_x, coef_e, coef_n,
                         *w, block_b=block_b, interpret=_interpret(interpret))
    return out[0] if squeeze else out
