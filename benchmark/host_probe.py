"""A fixed piece of host work, timed: how fast are the store's cores today?

`run.py` starts it pinned to the store's cores, sends one line just before
the window opens, and reads one line back: the least, in ms, of `REPEATS`
passes of the same pure-Python and numpy work (the shape of what the store's
host path does to a request: boxing 49,152 floats one by one, packing them,
one elementwise pass over a 64 x 768 block and a sort). It imports nothing of
the program and asks nothing of the store, so a slow machine reads high here
whatever the program under test does: a run's level is read beside it.
`/proc/stat` is empty on the chip's machine, hence work that is timed and no
counter that is read.
"""

from __future__ import annotations

import json
import sys
import time

import numpy as np

REPEATS = 15         # ~0.8 s in all; the least pass is the machine's speed
ROUNDS = 20          # of boxing a request's floats: ~50 ms a pass
FLOATS = 64 * 768


def one_pass(block: list) -> float:
    t0 = time.perf_counter()
    for _ in range(ROUNDS):
        boxed = [float(v) for v in block]           # pure Python, one GIL
        packed = np.asarray(boxed, np.float32)      # list -> array
    rows = packed.reshape(64, 768)
    np.sort((rows * rows).sum(axis=1))              # numpy, single thread
    return (time.perf_counter() - t0) * 1e3


def main() -> int:
    block = (np.arange(FLOATS, dtype=np.float32) % 97.0).tolist()
    one_pass(block)                                 # first touch of the code
    print(json.dumps({"ready": True}), flush=True)
    for line in sys.stdin:
        if json.loads(line)["cmd"] == "exit":
            break
        passes = [one_pass(block) for _ in range(REPEATS)]
        print(json.dumps({"least_ms": min(passes), "passes_ms": passes}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
