"""The one general traffic generator: a traffic file's parameters and the
seed in, the cell's inputs out. A new mix is a new data file, not code.

``kind`` says which family of parameters the file holds:

- ``chunk_stream``: ``pool_chunks`` distinct chunks of ``chunk_rows`` uint8
  images (side and channels from the configuration), every row different.
- ``repeat_fit``: one ``rows`` x ``features`` float32 matrix drawn N(0, 1)
  and a binary label from a fixed nonlinear rule plus logistic noise.

The same seed gives the same inputs; slabs are drawn from spawned child
seeds on a few threads so that set-up stays short.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

_THREADS = 8
_SLAB_ROWS = {"chunk_stream": 1024, "repeat_fit": 262144}


def _slabs(rows: int, slab: int) -> list:
    return [(lo, min(rows, lo + slab)) for lo in range(0, rows, slab)]


def _draw_all(jobs: list, draw: object) -> None:
    with ThreadPoolExecutor(max_workers=_THREADS) as pool:
        list(pool.map(lambda job: draw(*job), jobs))


def _chunk_stream(traffic: dict, config: dict, seed: int) -> dict:
    rows, side, ch = int(traffic["chunk_rows"]), int(config["image_size"]), int(config["channels"])
    chunks = [np.empty((rows, side, side, ch), np.uint8) for _ in range(int(traffic["pool_chunks"]))]
    jobs = []
    for c, chunk in enumerate(chunks):
        slabs = _slabs(rows, _SLAB_ROWS["chunk_stream"])
        children = np.random.SeedSequence([seed, 100, c]).spawn(len(slabs))
        jobs += [(chunk, lo, hi, child) for (lo, hi), child in zip(slabs, children)]

    def draw(chunk: np.ndarray, lo: int, hi: int, child: object) -> None:
        view = chunk[lo:hi].reshape(-1).view(np.uint64)  # 8 pixels a draw
        view[...] = np.random.default_rng(child).integers(
            0, 2 ** 64 - 1, size=view.size, dtype=np.uint64, endpoint=True)

    _draw_all(jobs, draw)
    return {"chunks": chunks}


def label_rule(x: np.ndarray) -> np.ndarray:
    """The fixed nonlinear rule of the Higgs stand-in: a logit of the first
    eight features (the rest are distractors, as the low-level Higgs
    features mostly are)."""
    return (1.2 * x[:, 0] - x[:, 1] * x[:, 2] + np.sin(2.0 * x[:, 3])
            + 0.5 * (x[:, 4] ** 2 - 1.0) + 0.8 * np.abs(x[:, 5]) * np.sign(x[:, 6])
            - 0.6 * x[:, 7])


def _repeat_fit(traffic: dict, config: dict, seed: int) -> dict:
    rows, d = int(traffic["rows"]), int(config["features"])
    x = np.empty((rows, d), np.float32)
    y = np.empty((rows,), np.int64)
    slabs = _slabs(rows, _SLAB_ROWS["repeat_fit"])
    children = np.random.SeedSequence([seed, 200]).spawn(len(slabs))

    def draw(lo: int, hi: int, child: object) -> None:
        rng = np.random.default_rng(child)
        x[lo:hi] = rng.standard_normal((hi - lo, d), np.float32)
        noise = rng.logistic(size=hi - lo)
        y[lo:hi] = (label_rule(x[lo:hi].astype(np.float64)) + noise > 0).astype(np.int64)

    _draw_all([(lo, hi, child) for (lo, hi), child in zip(slabs, children)], draw)
    return {"x": x, "y": y}


_KINDS = {"chunk_stream": _chunk_stream, "repeat_fit": _repeat_fit}


def generate(traffic: dict, config: dict, seed: int) -> dict:
    kind = traffic.get("kind")
    if kind not in _KINDS:
        raise KeyError(f"traffic kind {kind!r}: chipbench/traffic_gen.py knows {sorted(_KINDS)}")
    return _KINDS[kind](traffic, config, int(seed))
