"""The one general traffic generator: a mix is a data file, never code.

``benchmark/traffic/<mix>.json`` names a ``driver`` (a file under
``benchmark/drivers/``) and its parameters.  Everything drawn comes from
``--seed`` through one ``numpy.random.RandomState``; every seed gets the
SAME multiset of sizes and arrival gaps in another order (drawn once from
the mix's own ``shape_seed``, then permuted by ``--seed``), so that a seed
changes the order of the work and not its amount.

Serving mixes
    ``prompt_tokens`` / ``new_tokens``: ``{"dist": "lognormal", "median",
    "sigma", "min", "max"}`` or ``{"dist": "uniform", "min", "max"}``;
    ``arrivals``: ``{"kind": "poisson", "rate_per_s": r}`` (open loop) or
    ``{"kind": "backlog", "depth": d, "requests_per_s_bound": b}`` (the
    queue is kept ``d`` deep; ``b * seconds`` requests make one lap).
Training mixes
    ``batch``, ``seq``, ``layout`` (``mlm_synthetic``: 15% of positions
    masked, the label a function of the position — copied from
    ``examples/bert/pretrain_bert.py::synthetic_mlm_batch``, per-row loop
    and all, because that loop is the example's input pipeline).
"""
from __future__ import annotations

import dataclasses

import numpy as np

_SEED_MASK = 0xFFFFFFFF        # RandomState takes 32 bits
_LAP_STREAM = 1 << 16          # lap k of a backlog draws from stream this + k


def rng_for(seed: int, stream: int = 0) -> np.random.RandomState:
    seed = int(seed)
    return np.random.RandomState(
        [seed & _SEED_MASK, (seed >> 32) & _SEED_MASK, stream])


def _draw(spec: dict, n: int, rng) -> np.ndarray:
    if spec["dist"] == "lognormal":
        x = np.exp(rng.normal(np.log(spec["median"]), spec["sigma"], n))
    elif spec["dist"] == "uniform":
        x = rng.uniform(spec["min"], spec["max"] + 1, n)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.floor(x), spec["min"], spec["max"]).astype(np.int64)


@dataclasses.dataclass
class Request:
    index: int
    due_s: float               # offset from the window's start
    prompt: np.ndarray         # int32 token ids
    new_tokens: int


def serve_requests(mix: dict, seed: int, seconds: float, vocab: int):
    """The requests of one window, in the order they are due: a list (open
    loop) or a ``Backlog``, which indexes and iterates like one.

    Open loop: as many as ``rate * seconds`` (a fixed count — the gaps are
    exponential draws scaled to fill the window exactly, so every seed
    offers the same load).  Backlog: a ``Backlog`` whose lap is
    ``requests_per_s_bound * seconds`` requests, all due at 0; the driver
    keeps the queue ``depth`` deep and drops what the window never
    reached."""
    arr = mix["arrivals"]
    if arr["kind"] == "poisson":
        n = max(1, int(round(arr["rate_per_s"] * seconds)))
    elif arr["kind"] == "backlog":
        n = max(1, int(np.ceil(arr["requests_per_s_bound"] * seconds)))
    else:
        raise ValueError(f"unknown arrivals {arr['kind']!r}")
    shape = rng_for(mix["shape_seed"], stream=n)
    prompts = _draw(mix["prompt_tokens"], n, shape)
    news = _draw(mix["new_tokens"], n, shape)
    gaps = shape.exponential(1.0, n)
    rng = rng_for(seed)
    order = rng.permutation(n)
    prompts, news = prompts[order], news[order]
    if arr["kind"] == "poisson":
        gaps = gaps[rng.permutation(n)]
        due = np.cumsum(gaps) - gaps[0]
        due = due * (seconds * (n - 1) / n) / max(due[-1], 1e-9)
    else:
        due = np.zeros(n)
    first = [Request(i, float(due[i]),
                     rng.randint(0, vocab, size=int(prompts[i]))
                     .astype(np.int32), int(news[i]))
             for i in range(n)]
    return first if arr["kind"] == "poisson" else Backlog(first, seed, vocab)


class Backlog:
    """A backlog that no server can drain.  It holds the first lap; an index
    past the end of what is made goes round again: the same sizes in the
    same order under fresh indices, each lap's prompts drawn from a stream
    of its own, so that no lap shares a prefix with another.  ``len`` and
    iteration see what has been made so far — at first the first lap."""

    def __init__(self, first: list, seed: int, vocab: int):
        self.made, self.lap = list(first), len(first)
        self.seed, self.vocab = seed, vocab
        self._rng = None

    def __len__(self) -> int:
        return len(self.made)

    def __iter__(self):
        return iter(self.made)

    def __getitem__(self, index: int) -> Request:
        while index >= len(self.made):
            lap, i = divmod(len(self.made), self.lap)
            if i == 0:
                self._rng = rng_for(self.seed, stream=_LAP_STREAM + lap)
            like = self.made[i]
            self.made.append(Request(
                len(self.made), 0.0, self._rng.randint(
                    0, self.vocab, size=len(like.prompt)).astype(np.int32),
                like.new_tokens))
        return self.made[index]


def greedy_sampling(mix: dict) -> bool:
    return mix["sampling"] == "greedy"


class TrainBatches:
    """Host-side batches for the training loop, one per ``next()``."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        if mix["layout"] != "mlm_synthetic":
            raise ValueError(f"unknown layout {mix['layout']!r}")
        self.batch, self.seq, self.vocab = mix["batch"], mix["seq"], vocab
        self.mask_share = mix["mask_share"]
        self.rng = rng_for(seed, stream=1)

    @property
    def tokens_per_step(self) -> int:
        return self.batch * self.seq

    @property
    def labels_per_row(self) -> int:
        return max(1, int(self.mask_share * self.seq))

    def next(self) -> dict:
        rng, seq = self.rng, self.seq
        tokens = rng.randint(4, self.vocab, size=(self.batch, seq))
        labels = np.full_like(tokens, -100)
        for i in range(self.batch):
            pos = rng.choice(np.arange(1, seq), size=self.labels_per_row,
                             replace=False)
            labels[i, pos] = (7 * pos + 13) % self.vocab
            tokens[i, pos] = 3
        return {"tokens": tokens.astype(np.int32),
                "labels": labels.astype(np.int32)}
