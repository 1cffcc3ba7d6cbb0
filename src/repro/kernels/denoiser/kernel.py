"""Fused diffusion-policy denoiser step (the paper's per-decision hot loop).

EAT runs T=10 sequential denoiser forward passes per scheduling decision
(Algorithm 1 lines 5–11); each pass is a small 2x256 Mish MLP. Launch
overhead and HBM round-trips between the three matmuls dominate at this
size, so we fuse concat(x, t_emb, f_s) -> fc1 -> mish -> fc2 -> mish ->
fc3 -> tanh into a single kernel: all weights (~0.5 MB) and activations stay
in VMEM, and the batch dimension is tiled across the grid.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _mish(x):
    return x * jnp.tanh(jax.nn.softplus(x))


def _pin(x):
    """Value-preserving FMA blocker (`env._pin`, replicated here so the
    kernel module stays import-light): the chain's affine update must emit
    the same mul/add sequence as the ref oracle in every compilation
    context."""
    return jnp.minimum(x, 1e30)


def _dot(a, b):
    """a @ b, float32 out, at the ambient matmul precision: the actor's
    precision is the caller's (`jax.default_matmul_precision`), the same
    for these kernels as for the XLA paths around them."""
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _denoiser_kernel(inp_ref, w1_ref, b1_ref, w2_ref, b2_ref, w3_ref, b3_ref,
                     out_ref):
    x = inp_ref[...].astype(jnp.float32)
    h = _mish(_dot(x, w1_ref[...].astype(jnp.float32)) + b1_ref[...])
    h = _mish(_dot(h, w2_ref[...].astype(jnp.float32)) + b2_ref[...])
    o = jnp.tanh(_dot(h, w3_ref[...].astype(jnp.float32)) + b3_ref[...])
    out_ref[...] = o.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def denoiser_step(inp, w1, b1, w2, b2, w3, b3, *, block_b: int = 128,
                  interpret: bool = True):
    """inp: (B, D_in) = concat(x_i, t_emb, f_s); returns eps (B, A)."""
    B, din = inp.shape
    h1 = w1.shape[1]
    h2 = w2.shape[1]
    a = w3.shape[1]
    block_b = min(block_b, B)
    bp = (-B) % block_b
    inp_p = jnp.pad(inp, ((0, bp), (0, 0)))
    nb = (B + bp) // block_b
    out = pl.pallas_call(
        _denoiser_kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((block_b, din), lambda i: (i, 0)),
            pl.BlockSpec((din, h1), lambda i: (0, 0)),
            pl.BlockSpec((h1,), lambda i: (0,)),
            pl.BlockSpec((h1, h2), lambda i: (0, 0)),
            pl.BlockSpec((h2,), lambda i: (0,)),
            pl.BlockSpec((h2, a), lambda i: (0, 0)),
            pl.BlockSpec((a,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_b, a), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((B + bp, a), inp.dtype),
        interpret=interpret,
    )(inp_p, w1, b1, w2, b2, w3, b3)
    return out[:B]


def _chain_kernel(x_ref, noises_ref, f_ref, temb_ref, coef_ref, w1_ref,
                  b1_ref, w2_ref, b2_ref, w3_ref, b3_ref, out_ref):
    """All K reverse steps for one batch block, weights resident across the
    whole chain — one kernel launch per decision instead of K. The (3, K)
    chain coefficients sit in SMEM: step j reads three scalars at a loop
    index, which a vector load from VMEM cannot do."""
    w1 = w1_ref[...].astype(jnp.float32)
    b1 = b1_ref[...]
    w2 = w2_ref[...].astype(jnp.float32)
    b2 = b2_ref[...]
    w3 = w3_ref[...].astype(jnp.float32)
    b3 = b3_ref[...]
    f = f_ref[...].astype(jnp.float32)
    K, t_dim = temb_ref.shape
    block_b = x_ref.shape[0]

    def step(j, x):
        t_b = jnp.broadcast_to(temb_ref[j], (block_b, t_dim))
        inp = jnp.concatenate([x, t_b, f], axis=-1)
        h = _mish(_dot(inp, w1) + b1)
        h = _mish(_dot(h, w2) + b2)
        eps = jnp.tanh(_dot(h, w3) + b3)
        return (_pin(coef_ref[0, j] * x) + _pin(coef_ref[1, j] * eps)
                + _pin(coef_ref[2, j] * noises_ref[j]))

    x0 = jax.lax.fori_loop(0, K, step, x_ref[...].astype(jnp.float32))
    out_ref[...] = jnp.tanh(x0).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def denoiser_chain(x, noises, f_s, tembs, coef_x, coef_e, coef_n,
                   w1, b1, w2, b2, w3, b3, *, block_b: int = 128,
                   interpret: bool = True):
    """Whole K-step reverse-diffusion chain as ONE kernel launch.

    x: (B, A) initial x_K; noises: (K, B, A); f_s: (B, F); tembs: (K, t_dim);
    coef_*: (K,) affine chain coefficients (see `actors.samplers`). Returns
    tanh(x_0) (B, A) — bitwise-identical to `ref.denoiser_chain_ref` on the
    same inputs (tests/test_actors.py).
    """
    B, a = x.shape
    K = tembs.shape[0]
    fdim = f_s.shape[1]
    t_dim = tembs.shape[1]
    h1 = w1.shape[1]
    h2 = w2.shape[1]
    block_b = min(block_b, B)
    bp = (-B) % block_b
    x_p = jnp.pad(x, ((0, bp), (0, 0)))
    n_p = jnp.pad(noises, ((0, 0), (0, bp), (0, 0)))
    f_p = jnp.pad(f_s, ((0, bp), (0, 0)))
    nb = (B + bp) // block_b
    out = pl.pallas_call(
        _chain_kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((block_b, a), lambda i: (i, 0)),
            pl.BlockSpec((K, block_b, a), lambda i: (0, i, 0)),
            pl.BlockSpec((block_b, fdim), lambda i: (i, 0)),
            pl.BlockSpec((K, t_dim), lambda i: (0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((a + t_dim + fdim, h1), lambda i: (0, 0)),
            pl.BlockSpec((h1,), lambda i: (0,)),
            pl.BlockSpec((h1, h2), lambda i: (0, 0)),
            pl.BlockSpec((h2,), lambda i: (0,)),
            pl.BlockSpec((h2, a), lambda i: (0, 0)),
            pl.BlockSpec((a,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_b, a), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((B + bp, a), x.dtype),
        interpret=interpret,
    )(x_p, n_p, f_p, tembs, jnp.stack([coef_x, coef_e, coef_n]),
      w1, b1, w2, b2, w3, b3)
    return out[:B]
