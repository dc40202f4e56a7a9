"""Open loop: Poisson arrivals at ``rate_per_s``, each request sent at its
due time whatever the answers before it."""

import numpy as np

from chipbench.bench import stream_rng


def poisson_offsets(rate: float, seconds: float, seed: int) -> np.ndarray:
    """Due times of a Poisson stream at ``rate`` over ``seconds``: the
    quantiles of the exponential gap, shuffled by the seed and scaled to
    fill the window, so every seed sends the same number of requests with
    the same gaps in another order."""
    n = max(1, int(round(rate * seconds)))
    q = (np.arange(n) + 0.5) / n
    gaps = stream_rng(seed, 0).permutation(-np.log1p(-q) / rate)
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return due * (seconds / (due[-1] + gaps[-1]))


def offsets(traffic: dict, seconds: float, seed: int):
    return poisson_offsets(float(traffic["rate_per_s"]), seconds, seed)


def concurrency(traffic: dict):
    """Requests in flight at most: unbounded."""
    return None


def drive(send, draw, traffic: dict, t0: float, t_end: float, span) -> None:
    for it, off in zip(draw.items, draw.offsets):
        send(it, t0 + off)
