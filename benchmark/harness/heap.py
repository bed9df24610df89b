"""The window opens on a settled heap: a declared assumption, not a cure
that is understood.

What was seen (PERF.md section 6, PR 25): in a fresh process 9 of 39 windows
held one stall of 1-4 s (one serving tick, or a few training steps); with
``settle`` at the end of set-up, 0 of 46. The cause is NOT proved. Set-up
leaves millions of objects on the heap (the traced programs of a 36-layer
model, the compiled steps' caches), so a full collection was the suspect,
but a timed full collection took 0.1 s, and one run showed a 4.1 s tick
with 0.001 s of collections in its window. So every number of the benchmark
is taken in this process state, which a user of ``Scheduler`` or
``CompiledStep`` gets only by doing the same, and a stall that unsettled
processes hit moves no metric here: it is listed in PERF.md section 7 as
the program's to explain. ``Pauses`` times the collections that still
happen, and ``tools.py --vary`` runs its windows unsettled with a watchdog
that writes the Python stacks of a tick that takes over a second."""
from __future__ import annotations

import gc
import time


def settle():
    gc.collect()
    gc.freeze()


class Pauses:
    """``with Pauses() as p``: every collection in the block, timed."""

    def __init__(self):
        self.pauses = []  # (seconds, generation)
        self._began = None

    def __call__(self, phase, info):
        if phase == "start":
            self._began = time.perf_counter()
        elif self._began is not None:
            self.pauses.append((time.perf_counter() - self._began,
                                info["generation"]))

    def __enter__(self):
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self)

    def facts(self):
        return {"gc_pauses_s": round(sum(p for p, _ in self.pauses), 3),
                "gc_longest_s_gen": max(self.pauses, default=(0.0, 0))}
