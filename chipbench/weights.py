"""The actor's weights, made by the benchmark on the device from a key.

The layout is the one the program's actor reads and the plain reference
reads too: an attention encoder (`enc`: wq, wk, wv of rows x d_attn, wo of
d_attn), the DDPM denoiser's Mish MLP (`denoiser.layers`: [A + t_dim + F,
hidden, hidden, A]) and the Gaussian head (`sigma_head`: w of A x A, b).
Weights are N(0, 1/fan_in), biases N(0, 0.01^2), the head's bias the
constant `sigma_bias`.
"""
from __future__ import annotations

import functools
import math
from typing import Dict

import jax
import jax.numpy as jnp


@functools.lru_cache(maxsize=None)
def _maker(rows: int, cols: int, A: int, d_attn: int, hidden: int,
           t_dim: int, sigma_std: float, sigma_bias: float):
    dims = [A + t_dim + cols, hidden, hidden, A]

    @jax.jit
    def make(key):
        ks = iter(jax.random.split(key, 16))
        n = lambda shape, std: std * jax.random.normal(next(ks), shape)  # noqa: E731
        enc = {w: n((rows, d_attn), 1 / math.sqrt(rows))
               for w in ("wq", "wk", "wv")}
        enc["wo"] = n((d_attn,), 1 / math.sqrt(d_attn))
        layers = [{"w": n((a, b), 1 / math.sqrt(a)), "b": n((b,), 0.01)}
                  for a, b in zip(dims[:-1], dims[1:])]
        return {"enc": enc, "denoiser": {"layers": layers},
                "sigma_head": {"w": n((A, A), sigma_std),
                               "b": jnp.full((A,), sigma_bias, jnp.float32)}}
    return make


def make_actor(actor: Dict, rows: int, cols: int, A: int, key) -> Dict:
    return _maker(rows, cols, A, int(actor["d_attn"]), int(actor["hidden"]),
                  int(actor["t_dim"]), float(actor["sigma_std"]),
                  float(actor["sigma_bias"]))(key)
