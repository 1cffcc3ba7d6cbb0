"""Plain reference of a served qwen2 task (arXiv:2407.10671, Qwen2-1.5B).

The model: token embedding (tied with the output head), `num_hidden_layers`
pre-norm blocks of RMSNorm, grouped-query attention with q/k/v biases and
rotary positions (rotate-half, base `rope_theta`), RMSNorm and a SwiGLU
feed-forward, then a final RMSNorm and the head. Computed in float32 with
every matrix product at `highest`, one `lax.scan` over layers, from the
bfloat16 weights as served.

The served task (the paper's patch-parallel generation, DistriFusion's
mapping): a prompt of S tokens, left-padded with token 0 to a multiple of
the gang size c, is split into c consecutive chunks that are prefilled
independently, each with its own positions 0..S/c-1 and no attention across
chunks; decoding then continues at positions S, S+1, ... and attends to
every prompt token and every earlier decoded token. The served tokens are
greedy: token 0 from the last prompt position, token i from decoding token
i-1. `gaps` says, for each served token, how far its logit lies below the
best logit at that position.

Nothing here imports the program. The weights come from `make_weights`,
the benchmark's own generator, from the same key the benchmark handed the
program's loader.
"""
from __future__ import annotations

import functools
import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np


def dims(cfg: Dict) -> Dict[str, int]:
    d = cfg
    hd = d["hidden_size"] // d["num_attention_heads"]
    V = d["vocab_size"]
    return {"L": d["num_hidden_layers"], "D": d["hidden_size"],
            "H": d["num_attention_heads"], "KV": d["num_key_value_heads"],
            "hd": hd, "F": d["intermediate_size"], "V": V,
            "Vp": -(-V // 256) * 256, "theta": float(d["rope_theta"]),
            "eps": float(d["rms_norm_eps"])}


def weight_shapes(m: Dict[str, int]) -> Dict[str, tuple]:
    L, D, H, KV, hd, F = m["L"], m["D"], m["H"], m["KV"], m["hd"], m["F"]
    return {"embed": (m["Vp"], D), "final_norm": (D,),
            "ln1": (L, D), "wq": (L, D, H * hd), "bq": (L, H * hd),
            "wk": (L, D, KV * hd), "bk": (L, KV * hd),
            "wv": (L, D, KV * hd), "bv": (L, KV * hd),
            "wo": (L, H * hd, D), "ln2": (L, D),
            "w_gate": (L, D, F), "w_up": (L, D, F), "w_down": (L, F, D)}


def weight_std(m: Dict[str, int], name: str) -> float:
    D, F, L = m["D"], m["F"], m["L"]
    return {"embed": 0.02, "final_norm": 0.1, "ln1": 0.1, "ln2": 0.1,
            "bq": 0.02, "bk": 0.02, "bv": 0.02,
            "wo": 0.02 / math.sqrt(2 * L), "w_down": 1 / math.sqrt(F)
            }.get(name, 1 / math.sqrt(D))


@functools.lru_cache(maxsize=None)
def weight_maker(cfg_key: tuple, dtype_name: str):
    """One jitted call that makes every weight from a key, on the device,
    in the dtype served. Norm scales are 1 + N(0, 0.1^2), the rest N(0,
    std^2); each leaf draws from `fold_in(key, its index)`."""
    m = dict(cfg_key)
    shapes = weight_shapes(m)
    dtype = jnp.dtype(dtype_name)

    @jax.jit
    def make(key):
        out = {}
        for i, (name, shp) in enumerate(sorted(shapes.items())):
            w = weight_std(m, name) * jax.random.normal(
                jax.random.fold_in(key, i), shp, jnp.float32)
            if name in ("final_norm", "ln1", "ln2"):
                w = 1.0 + w
            out[name] = w.astype(dtype)
        return out
    return make


def make_weights(m: Dict[str, int], key, dtype="bfloat16"):
    return weight_maker(tuple(sorted(m.items())), str(dtype))(key)


def fp8_round(x):
    """Round float32 values to float8 e4m3 (3 mantissa bits, smallest
    normal 2^-6, largest 448), kept as float32."""
    ax = jnp.abs(x)
    e = jnp.floor(jnp.log2(jnp.maximum(ax, 1e-30)))
    quantum = jnp.exp2(jnp.maximum(e, -6.0) - 3.0)
    return jnp.clip(jnp.round(x / quantum) * quantum, -448.0, 448.0)


def fp8(x):
    """Per-tensor scaled e4m3: the control's weights and matmul inputs."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return fp8_round(x / s) * s


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, None, None].astype(jnp.float32) * freqs
    x1, x2 = jnp.split(x, 2, axis=-1)
    c, s = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


@functools.lru_cache(maxsize=None)
def _forward(cfg_key: tuple, rows: int, quant: bool):
    m = dict(cfg_key)
    H, KV, hd, eps, theta, V = (m["H"], m["KV"], m["hd"], m["eps"],
                                m["theta"], m["V"])
    q8 = fp8 if quant else (lambda x: x)

    def mm(x, w):
        return q8(x) @ q8(w)

    @jax.jit
    def fwd(w, tokens, pos, mask, out_rows):
        with jax.default_matmul_precision("highest"):
            f32 = lambda a: a.astype(jnp.float32)          # noqa: E731
            x = f32(w["embed"])[tokens]
            layers = {k: w[k] for k in ("ln1", "wq", "bq", "wk", "bk", "wv",
                                        "bv", "wo", "ln2", "w_gate", "w_up",
                                        "w_down")}

            def block(x, p):
                p = {k: f32(v) for k, v in p.items()}
                h = _rms(x, p["ln1"], eps)
                q = (mm(h, p["wq"]) + p["bq"]).reshape(rows, H, hd)
                k = (mm(h, p["wk"]) + p["bk"]).reshape(rows, KV, hd)
                v = (mm(h, p["wv"]) + p["bv"]).reshape(rows, KV, hd)
                q, k = _rope(q, pos, theta), _rope(k, pos, theta)
                k = jnp.repeat(k, H // KV, axis=1)
                v = jnp.repeat(v, H // KV, axis=1)
                s = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)
                s = jnp.where(mask[None], s, -jnp.inf)
                o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
                x = x + mm(o.reshape(rows, H * hd), p["wo"])
                h = _rms(x, p["ln2"], eps)
                g = jax.nn.silu(mm(h, p["w_gate"])) * mm(h, p["w_up"])
                return x + mm(g, p["w_down"]), None

            x, _ = jax.lax.scan(block, x, layers)
            x = _rms(x[out_rows], f32(w["final_norm"]), eps)
            return mm(x, f32(w["embed"]).T)[:, :V]
    return fwd


def layout(prompt: np.ndarray, c: int, served: np.ndarray, rows: int):
    """Tokens, positions and attention mask of one served task, padded to
    `rows`, and the rows whose logits chose each served token."""
    c = max(int(c), 1)
    pad = (-len(prompt)) % c
    p = np.concatenate([np.zeros(pad, np.int32), np.asarray(prompt, np.int32)])
    S, n = len(p), len(served)
    L = S // c
    tokens = np.zeros(rows, np.int32)
    tokens[:S] = p
    tokens[S:S + n - 1] = served[:-1]
    pos = np.zeros(rows, np.int32)
    pos[:S] = np.arange(S) % L
    pos[S:] = S + np.arange(rows - S)
    r = np.arange(rows)
    chunk = np.where(r < S, r // L, -1)
    mask = (r[None, :] <= r[:, None]) & (
        (chunk[:, None] == chunk[None, :]) | (r[:, None] >= S))
    mask &= ~((r[None, :] >= S + n - 1) & (r[None, :] != r[:, None]))
    out_rows = np.concatenate([[S - 1], S + np.arange(n - 1)]).astype(np.int32)
    return tokens, pos, mask, out_rows


def logits(m: Dict[str, int], weights, prompt, c, served, rows: int,
           quant: bool = False) -> np.ndarray:
    """(len(served), V) float32 logits at the positions that chose the
    served tokens; `quant=True` is the float8 control."""
    tokens, pos, mask, out_rows = layout(prompt, c, np.asarray(served), rows)
    out = np.zeros(rows, np.int32)
    out[:len(out_rows)] = out_rows
    fwd = _forward(tuple(sorted(m.items())), rows, bool(quant))
    lg = fwd(weights, jnp.asarray(tokens), jnp.asarray(pos),
             jnp.asarray(mask), jnp.asarray(out))
    return np.asarray(lg, np.float64)[:len(out_rows)]


def gaps(ref_logits: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """How far each token's logit lies below the best at its position."""
    t = np.asarray(tokens, np.int64)
    return ref_logits.max(axis=1) - ref_logits[np.arange(len(t)), t]


def program_tree(w: Dict) -> Dict:
    """These weights under the names of the served program's parameter
    tree: one stacked period per layer, the head tied to the embedding. The
    serve driver checks its shapes against the program's before it runs."""
    lin = lambda n, b=None: ({"w": w[n], "b": w[b]} if b else {"w": w[n]})  # noqa: E731
    return {"embed": {"table": w["embed"]},
            "final_norm": {"scale": w["final_norm"]},
            "periods": {"norm0_mix": {"scale": w["ln1"]},
                        "blk0_attn": {"wq": lin("wq", "bq"),
                                      "wk": lin("wk", "bk"),
                                      "wv": lin("wv", "bv"),
                                      "wo": lin("wo")},
                        "norm0_ffn": {"scale": w["ln2"]},
                        "blk0_ffn": {"gate": lin("w_gate"), "up": lin("w_up"),
                                     "down": lin("w_down")}}}
