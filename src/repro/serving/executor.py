"""Real prefill/decode execution for the serving layer.

`ModelExecutor` owns the model zoo instances (reduced or full configs), the
per-arch jitted init/prefill/decode programs, and the generation loop the
engine and the serving backend both call. Reduced configs are the CPU test
models and run in float32, which their bitwise pins need; published widths
serve in bfloat16, the zoo's own compute dtype (one qwen2-1.5b copy is
3.1 GB there, 6.2 GB in float32). Two correctness properties live here:

KV-cache sizing. The scheduler picks the inference-step count (up to
``s_max``) independently of the request's ``max_new_tokens``; the decode
loop runs ``steps`` iterations, so the cache is sized by
``max(steps, max_new_tokens)`` — the legacy engine sized it by
``max_new_tokens`` alone and silently overflowed the cache once the policy
chose more steps (decode writes past capacity clamp at the boundary).

Patch-parallel prefill. A c_k-patch task splits its prompt into c_k chunks
prefilled as a batch dimension — the DistriFusion patch mapping: each chunk
is one gang member's patch, computed in parallel with no cross-patch
attention (chunk-local RoPE positions come for free from the per-row
``arange(s)`` in `blocks.attn_prefill`). The per-chunk KV caches then merge
back into one sequence-ordered cache (a pure reshape) that decode attends
over. For ``c == 1`` the chunked path is bitwise-identical to the unchunked
one (same positions, same flash-attention block shapes, same cache content)
— tests pin this. Architectures whose caches are not pure attention KV
(SSM/hybrid recurrent state, sliding-window rings, audio/vision frontends)
fall back to the unchunked prefill; the Table-VI latency model still
accounts the parallel speedup either way.
"""
from __future__ import annotations

import time
from typing import Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.config import get_config
from repro.faults.inject import ExecutorTimeout
from repro.models.lm import period_spec
from repro.models.zoo import Model, build_model
from repro.telemetry.trace import NULL_TRACER

# decode-capacity rounding: buckets cache shapes so jit re-traces per
# capacity bucket, not per (steps, max_new_tokens) pair. Value-safe: decode
# attention masks entries at or beyond `pos` (`attention.decode_attention`).
_CAP_ROUND = 8


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def chunkable(cfg) -> bool:
    """True when the patch-parallel (batched-chunk) prefill applies: every
    mixer is plain full attention (KV merge is a reshape) and no frontend
    tokens are prepended per batch row."""
    if cfg.family == "audio" or cfg.frontend != "none":
        return False
    if cfg.sliding_window:
        return False
    return all(mixer == "attn" for mixer, _f in period_spec(cfg))


def _merge_chunk_cache(model: Model, ccache: Dict, S_pad: int,
                       capacity: int, dtype) -> Dict:
    """(c, chunk)-batched prefill caches -> one (1, capacity) decode cache.

    Chunks are consecutive prompt slices, so concatenating their KV along
    the sequence axis — a reshape of (periods, c, chunk, kv, hd) — restores
    prompt order exactly; `pos = S_pad` points decode past the merged KV."""
    big = model.make_cache(1, capacity, dtype=dtype)

    def merge(dst, src):
        npd, c, chunk, nk, hd = src.shape
        flat = src.reshape(npd, 1, c * chunk, nk, hd)
        return jax.lax.dynamic_update_slice_in_dim(
            dst, flat.astype(dst.dtype), 0, axis=2)

    periods = jax.tree_util.tree_map(merge, big["periods"],
                                     ccache["periods"])
    return {"periods": periods, "pos": jnp.asarray(S_pad, jnp.int32)}


class _Programs(NamedTuple):
    """One arch's jitted programs. Each returns the next greedy token with
    the cache, so a generate step dispatches one program and nothing else."""
    init: object
    prefill: object           # (params, batch, capacity=) -> (tok, cache)
    prefill_chunked: object   # (params, chunks, capacity=) -> (tok, cache)
    decode: object            # (params, cache, tok) -> (tok, cache)


def _programs(model: Model, dtype) -> _Programs:
    vocab = model.cfg.vocab_size

    def next_tok(logits):
        return jnp.argmax(logits[:, -1:, :vocab], axis=-1).astype(jnp.int32)

    def prefill(params, batch, capacity):
        cache = model.make_cache(1, capacity, dtype=dtype)
        logits, cache = model.prefill(params, batch, cache,
                                      compute_dtype=dtype)
        return next_tok(logits), cache

    def prefill_chunked(params, chunks, capacity):
        c, chunk = chunks.shape
        ccache = model.make_cache(c, chunk, dtype=dtype)
        logits, ccache = model.prefill(params, {"tokens": chunks}, ccache,
                                       compute_dtype=dtype)
        cache = _merge_chunk_cache(model, ccache, c * chunk, capacity, dtype)
        # the prompt's last token ends chunk c-1
        return next_tok(logits[-1:]), cache

    def decode(params, cache, tok):
        logits, cache = model.decode(params, cache, tok, compute_dtype=dtype)
        return next_tok(logits), cache

    return _Programs(
        init=jax.jit(lambda key: model.init(key, dtype)),
        prefill=jax.jit(prefill, static_argnames=("capacity",)),
        prefill_chunked=jax.jit(prefill_chunked,
                                static_argnames=("capacity",)),
        decode=jax.jit(decode))


class ModelExecutor:
    """Cached models + jitted inference programs, shared by every server.

    One executor per engine/backend: all gang leaders of the same arch run
    through the same compiled prefill/decode programs (shapes permitting),
    so a stream pays tracing once per (arch, shape bucket)."""

    def __init__(self, reduced: bool = True, tracer=None):
        self.reduced = reduced
        self.dtype = jnp.float32 if reduced else jnp.bfloat16
        self.tracer = NULL_TRACER if tracer is None else tracer
        self._models: Dict[str, Model] = {}
        self._progs: Dict[str, _Programs] = {}
        self._warmed: set = set()                   # shape buckets compiled

    def model(self, arch: str) -> Model:
        if arch not in self._models:
            cfg = get_config(arch)
            model = build_model(cfg.reduced() if self.reduced else cfg)
            self._models[arch] = model
            self._progs[arch] = _programs(model, self.dtype)
        return self._models[arch]

    def init_params(self, arch: str, key):
        """Real weight materialisation — the cold-start cost being scheduled
        around (the Table-VI init_time stands in for its wall-clock)."""
        self.model(arch)
        return self._progs[arch].init(key)

    # ------------------------------------------------------------------
    def shape_key(self, arch: str, prompt_len: int, c: int, steps: int,
                  max_new_tokens: int) -> tuple:
        """The compilation bucket a `generate` call lands in: jit retraces
        once per (arch, chunk shape, cache capacity), so two calls with the
        same key reuse the same compiled prefill + decode programs."""
        c = max(int(c), 1)
        S_pad = int(prompt_len) + ((-int(prompt_len)) % c)
        capacity = S_pad + _round_up(max(int(steps), int(max_new_tokens)),
                                     _CAP_ROUND)
        use_chunked = chunkable(self.model(arch).cfg)
        return (arch, c if use_chunked else 1, S_pad, capacity)

    def warm(self, arch: str, prompt_len: int, c: int, steps: int,
             max_new_tokens: int) -> bool:
        """Pre-compile the init/prefill/decode programs a `generate` with
        these arguments would hit; returns True when compilation ran.

        Compiles from shapes alone (`jax.eval_shape` parameters and cache),
        so warming holds no weights on the device; jit's cache then serves
        the later calls with real arrays. Draws no key from the serving
        backend's streams, so warmed and unwarmed runs schedule
        identically."""
        k = self.shape_key(arch, prompt_len, c, steps, max_new_tokens)
        if k in self._warmed:
            return False
        _arch, cc, S_pad, capacity = k
        progs = self._progs[arch]
        key = jax.random.PRNGKey(0)
        if not any(w[0] == arch for w in self._warmed):
            progs.init.lower(key).compile()
        params = jax.eval_shape(progs.init, key)
        cfg = self.model(arch).cfg
        if chunkable(cfg):
            prefill = progs.prefill_chunked
            batch = jnp.zeros((cc, S_pad // cc), jnp.int32)
        else:       # the unchunked path prefills the unpadded prompt
            prefill = progs.prefill
            batch = self._full_batch(cfg, np.zeros(int(prompt_len), np.int32))
        prefill.lower(params, batch, capacity=capacity).compile()
        tok, cache = jax.eval_shape(
            lambda p, b: prefill(p, b, capacity=capacity), params, batch)
        progs.decode.lower(params, cache, tok).compile()
        self._warmed.add(k)
        return True

    # ------------------------------------------------------------------
    def _full_batch(self, cfg, prompt: np.ndarray) -> Dict:
        batch = {"tokens": jnp.asarray(prompt[None])}
        if cfg.frontend == "vision":
            batch["image_embeds"] = jnp.zeros((1, cfg.frontend_tokens,
                                               cfg.frontend_dim))
        if cfg.family == "audio":
            batch["frames"] = jnp.zeros((1, cfg.frontend_tokens, cfg.d_model))
        return batch

    def generate(self, arch: str, params, prompt, c: int, steps: int,
                 max_new_tokens: int = 16, *,
                 force_chunked: Optional[bool] = None,
                 deadline_s: float = 0.0) -> np.ndarray:
        """Greedy generation of `steps` tokens on a c-patch gang's params.

        `force_chunked` overrides the c>1 chunking heuristic (tests assert
        the c=1 chunked path is bitwise-identical to the unchunked one).
        `deadline_s > 0` bounds the attempt's wall clock: the decode loop
        checks the budget once per iteration and raises
        `faults.ExecutorTimeout` when exceeded (the retry/degrade wrapper
        in `serving.backend` catches it); 0 disables the check."""
        t_start = time.perf_counter()
        model = self.model(arch)
        progs = self._progs[arch]
        cfg = model.cfg
        prompt = np.asarray(prompt, np.int32)
        c = max(int(c), 1)
        steps = int(steps)
        pad = (-len(prompt)) % c
        S_pad = len(prompt) + pad
        capacity = S_pad + _round_up(max(steps, int(max_new_tokens)),
                                     _CAP_ROUND)
        use_chunked = (chunkable(cfg) if force_chunked is None
                       else force_chunked)
        tr = self.tracer
        with tr.span("prefill", cat="serving", arch=arch, c=c,
                     seq=S_pad, chunked=bool(use_chunked)):
            if use_chunked:
                # left-pad so the prompt's true final token ends the last
                # chunk — its last-position logits are the next-token
                # distribution
                chunks = jnp.asarray(np.pad(prompt, (pad, 0)).reshape(c, -1))
                tok, cache = progs.prefill_chunked(params, chunks,
                                                   capacity=capacity)
            else:
                tok, cache = progs.prefill(
                    params, self._full_batch(cfg, prompt), capacity=capacity)
            if tr.enabled:   # wall attribution only: sync inside the span
                jax.block_until_ready(tok)
        out = []
        with tr.span("decode", cat="serving", arch=arch, steps=steps,
                     capacity=capacity):
            for i in range(steps):
                if deadline_s > 0.0 \
                        and time.perf_counter() - t_start > deadline_s:
                    raise ExecutorTimeout(
                        f"{arch} generate exceeded {deadline_s:.1f}s "
                        f"budget at decode step {i}/{steps}")
                out.append(int(np.asarray(tok)[0, 0]))
                tok, cache = progs.decode(params, cache, tok)
        return np.asarray(out, np.int32)
