"""Open-loop task streams, generated by the benchmark from the seed.

The draw copies the program's Poisson arrivals and task attributes
(`core/workload.py`, `traffic/arrivals.py`): exponential gaps at a fixed rate
per stream, a gang size c over a support with stated probabilities (sizes
above the cluster are dropped and the rest renormalised), a model id and a
Gaussian quality jitter. It is stratified: every chunk of every stream holds
the same multiset of gaps, gang sizes, model ids and jitters, namely the
quantiles of their distributions, in an order drawn from the seed. So every
seed brings the same work in another order, and runs of different seeds
differ by the order alone.

`StreamSource` hands the streams to the program through the `take(stream,
n)` interface of the program's task sources: absolute arrival times, c,
model and noise, `n` tasks at a time.
"""
from __future__ import annotations

import statistics
from typing import Dict, List, Sequence

import numpy as np

COLS = ("arr_time", "c", "model", "noise")


def stratified_counts(probs: Sequence[float], n: int) -> np.ndarray:
    """Whole counts summing to n in the proportions `probs` (largest
    remainder)."""
    p = np.asarray(probs, np.float64)
    p = p / p.sum()
    raw = p * n
    counts = np.floor(raw).astype(np.int64)
    short = n - int(counts.sum())
    order = np.argsort(-(raw - counts), kind="stable")
    counts[order[:short]] += 1
    return counts


def chunk_template(traffic: Dict, num_servers: int, n: int) -> Dict:
    """The multiset one chunk of n tasks of one stream holds, sorted."""
    q = (np.arange(n, dtype=np.float64) + 0.5) / n
    gaps = -np.log1p(-q) / float(traffic["rate_per_stream"])
    support = np.asarray(traffic["c_support"], np.int64)
    probs = np.asarray(traffic["c_probs"], np.float64)
    probs = np.where(support <= num_servers, probs, 0.0)
    c = np.repeat(support, stratified_counts(probs, n))
    models = int(traffic.get("num_models", 1))
    model = np.repeat(np.arange(models), stratified_counts([1.0] * models, n))
    nd = statistics.NormalDist()
    noise = float(traffic["quality_noise"]) * np.array(
        [nd.inv_cdf(x) for x in q])
    return {"gap": gaps, "c": c.astype(np.int32),
            "model": model.astype(np.int32), "noise": noise.astype(np.float32)}


class StreamSource:
    """B open-loop task streams. Chunk j of every stream is drawn at once
    from `default_rng([seed, j])`, so the streams depend on the seed alone,
    never on how fast the program consumes them. `reserve` draws ahead
    during set-up, so the measured window does not pay for the drawing."""

    def __init__(self, traffic: Dict, num_servers: int, seed: int,
                 streams: int, chunk: int = 1024):
        self.B, self.chunk, self.seed = int(streams), int(chunk), int(seed)
        self.template = chunk_template(traffic, num_servers, self.chunk)
        self.chunks: List[Dict[str, np.ndarray]] = []
        self.clock = np.zeros(self.B, np.float64)
        self.ptr = np.zeros(self.B, np.int64)

    def _grow(self) -> None:
        rng = np.random.default_rng([self.seed, len(self.chunks)])
        new = {}
        for col in ("gap", "c", "model", "noise"):
            tile = np.broadcast_to(self.template[col], (self.B, self.chunk))
            new[col] = rng.permuted(tile, axis=1)
        arr = self.clock[:, None] + np.cumsum(new.pop("gap"), axis=1)
        self.clock = arr[:, -1].copy()
        new["arr_time"] = arr
        self.chunks.append(new)

    def reserve(self, n: int) -> None:
        """Draw every stream's first n tasks now."""
        while len(self.chunks) * self.chunk < n:
            self._grow()

    def tasks(self, stream: int, start: int, n: int) -> Dict[str, np.ndarray]:
        """Tasks start .. start+n of one stream, without consuming them."""
        self.reserve(start + n)
        first, last = start // self.chunk, (start + n - 1) // self.chunk
        lo = start - first * self.chunk
        out = {}
        for c in COLS:
            parts = [self.chunks[j][c][stream] for j in range(first, last + 1)]
            whole = parts[0] if len(parts) == 1 else np.concatenate(parts)
            out[c] = whole[lo:lo + n]
        return out

    def take(self, stream: int, n: int) -> Dict[str, np.ndarray]:
        """Pop the next n tasks of one stream (arrival times absolute)."""
        out = self.tasks(stream, int(self.ptr[stream]), n)
        self.ptr[stream] += n
        return out
