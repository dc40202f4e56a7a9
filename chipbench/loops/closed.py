"""Closed loop: ``clients`` clients, each sending its next request when
its last is answered, until the window ends."""
import time
from concurrent.futures import FIRST_COMPLETED, wait


def offsets(traffic: dict, seconds: float, seed: int):
    return None


def concurrency(traffic: dict):
    return int(traffic["clients"])


def drive(send, draw, traffic: dict, t0: float, t_end: float, span) -> None:
    clients, items = int(traffic["clients"]), draw.items
    pending, k = set(), 0
    while time.perf_counter() < t_end:
        while len(pending) < clients:
            # sent at once: due when sent
            pending.add(send(items[k % len(items)], 0.0))
            k += 1
        with span("bench.wait"):
            _, pending = wait(pending, timeout=t_end - time.perf_counter(),
                              return_when=FIRST_COMPLETED)
