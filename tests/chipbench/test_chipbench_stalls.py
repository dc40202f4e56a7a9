"""The stall watch sees the interpreter lock held, and where."""
import gc
import time

import chipbench_testlib  # noqa: F401  (puts the repository root on sys.path)
from chipbench.stalls import StallWatch


def _hold_the_lock(seconds: float) -> None:
    """Keep the interpreter lock in one C call for about ``seconds``."""
    n = 10**6
    t0 = time.perf_counter()
    sum(range(n))
    per = time.perf_counter() - t0
    sum(range(int(n * seconds / max(per, 1e-9))))


def test_a_held_lock_is_a_stall_with_every_stack(tmp_path):
    with open(tmp_path / "stacks.txt", "w") as dump:
        with StallWatch(limit_s=0.1, dump=dump) as w:
            time.sleep(0.1)
            _hold_the_lock(0.6)
            time.sleep(0.1)
    assert w.stalls and max(s for _, s in w.stalls) > 0.2
    assert "_hold_the_lock" in (tmp_path / "stacks.txt").read_text()
    assert w.summary().startswith("host stalls inside the window over 0.1 s: ")


def test_no_stall_while_the_lock_is_free():
    with StallWatch(limit_s=0.5) as w:
        time.sleep(0.5)
    assert w.stalls == []
    assert gc.callbacks.count(w._gc) == 0
