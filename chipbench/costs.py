"""Operations and bytes the algorithms need, counted from shapes.

A multiply-add counts as two operations. Bytes are what has to cross HBM:
weights and state read, results written; intermediates that stay on chip
do not count. `least_seconds` is the roofline: the larger of operations
over peak FLOP/s and bytes over peak HBM bandwidth.
"""
from __future__ import annotations

from typing import Dict, Tuple


def least_seconds(ops: float, nbytes: float, peaks: Dict) -> Tuple[float, str]:
    """(least time, which bound sets it)."""
    t_ops = ops / peaks["bf16_flops"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (t_ops, "operations") if t_ops >= t_bytes else (t_bytes, "bytes")


# ------------------------------------------------------------- scheduler
def env_step_bytes(B: int, E: int, K: int, A: int, l: int) -> int:
    """One fused env step over B streams, 4-byte values. Read: the state
    (time, 4 per server, 6 per task, step count), 7 per-task constants, the
    action and the queue view (2l + K); written: the state, the next queue
    view (2l + K), the observation 3(E + l), reward and done."""
    state = 2 + 4 * E + 6 * K
    read = state + 7 * K + A + 2 * l + K
    write = state + 2 * l + K + 3 * (E + l) + 2
    return 4 * B * (read + write)


def env_step_ops(E: int, K: int, l: int) -> int:
    """Arithmetic of one decision: pairwise gang counts over servers, the
    per-task retire, queue, wait and reward terms, the scalar latency,
    quality and reward chain."""
    return 4 * E * E + 12 * K + 8 * l + 40


def actor_ops(rows: int, cols: int, A: int, d_attn: int, hidden: int,
              t_dim: int, T: int) -> int:
    """One decision of the EAT actor: attention encoder over `cols` tokens
    of `rows` features, T passes of the denoiser MLP, the Gaussian head."""
    enc = 2 * cols * rows * d_attn * 3 + 2 * 2 * cols * cols * d_attn \
        + 2 * cols * d_attn
    mlp = 2 * ((A + t_dim + cols) * hidden + hidden * hidden + hidden * A)
    return enc + T * mlp + 2 * A * A


def greedy_ops(E: int, K: int, l: int, grid: int = 9) -> int:
    """The greedy baseline simulates every candidate action once."""
    return (1 + grid * l) * env_step_ops(E, K, l)


# -------------------------------------------------------------- learner
def critic_ops(obs_dim: int, A: int, hidden: int) -> int:
    """One forward pass of a critic, the MLP [obs_dim + A, hidden, hidden,
    1], for one transition."""
    return 2 * ((obs_dim + A) * hidden + hidden * hidden + hidden)


def sac_update_ops(batch: int, rows: int, cols: int, A: int, d_attn: int,
                   hidden: int, t_dim: int, T: int, critic_hidden: int) -> int:
    """The matrix products of one SAC update over `batch` transitions, each
    pass once (no recompute). A backward pass that yields the weights'
    gradients and the inputs' costs twice its forward; one that yields the
    inputs' alone, once. The target: the actor at s' and two target critics
    forward. The critics: forward and backward each. The actor: the actor
    forward and backward, and the two updated critics forward and back to
    their action input."""
    actor = actor_ops(rows, cols, A, d_attn, hidden, t_dim, T)
    critic = critic_ops(rows * cols, A, critic_hidden)
    target = actor + 2 * critic
    critics = 2 * 3 * critic
    actor_loss = 3 * actor + 2 * 2 * critic
    return batch * (target + critics + actor_loss)


# --------------------------------------------------------------- serving
def _layer(m: Dict) -> Tuple[int, int]:
    """(matrix parameters, all parameters) of one transformer layer."""
    D, H, KV, hd, F = m["D"], m["H"], m["KV"], m["hd"], m["F"]
    mats = D * (H + 2 * KV) * hd + H * hd * D + 3 * D * F
    return mats, mats + (H + 2 * KV) * hd + 2 * D


def weight_bytes(m: Dict, itemsize: int = 2) -> int:
    """Every weight read once: the layers, the final norm and the tied
    embedding, which the head reads whole."""
    return itemsize * (m["L"] * _layer(m)[1] + m["D"] + m["Vp"] * m["D"])


def prefill_cost(m: Dict, c: int, S: int, itemsize: int = 2) -> Tuple[int, int]:
    """(ops, bytes) of a patch-parallel prefill: c chunks of S/c tokens,
    causal attention within each chunk, logits for the last token."""
    L, D, H, KV, hd, V = m["L"], m["D"], m["H"], m["KV"], m["hd"], m["V"]
    n = S // c
    mats = _layer(m)[0]
    attn = 2 * 2 * H * hd * c * n * (n + 1) // 2
    ops = L * (2 * S * mats + attn) + 2 * D * V
    kv = L * S * 2 * KV * hd * itemsize
    return ops, weight_bytes(m, itemsize) + kv + 4 * S


def decode_cost(m: Dict, pos: int, itemsize: int = 2) -> Tuple[int, int]:
    """(ops, bytes) of one decode step at position `pos`: every weight and
    the pos + 1 cached keys and values of every layer read, one written."""
    L, D, H, KV, hd, V = m["L"], m["D"], m["H"], m["KV"], m["hd"], m["V"]
    ops = L * (2 * _layer(m)[0] + 2 * 2 * H * hd * (pos + 1)) + 2 * D * V
    kv = L * (pos + 2) * 2 * KV * hd * itemsize
    return ops, weight_bytes(m, itemsize) + kv
