"""The precision control and the planted faults that `correct` must catch.

None of these runs in a benchmark run.  `bench/control.py` reads them on
the chip, at a cell's own size, to set the limits in `bench/cells/`; the
CPU tests in `bench/tests/` check that each one turns `correct` false.

control       the program's own lower-precision path in place of the
              configured one: the fp32 store becomes a bf16 store, so
              verify ranks and reports distances on bf16 rows.
stale         a batch returns the previous batch's answers: a step that
              returns its state unchanged.
half_batch    the second half of each batch gets the first half's answers.
alter_answer  every answer's nearest id is moved to the next corpus row.
misroute      each request gets the answer of the next one in its batch.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.store import make_store

from .serving import VectorEngine

FAULTS = ("stale", "half_batch", "alter_answer", "misroute")
VARIANTS = ("sound", "control") + FAULTS


def control_index(index, X, config: dict):
    """(index, config) for the precision control of a built index: its
    fp32 store, the configuration's precision, served as bf16."""
    if config["store"] != "fp32":
        raise ValueError(f"no precision control for a {config['store']!r} "
                         f"store")
    return (dataclasses.replace(index, store=make_store("bf16", X)),
            {**config, "store": "bf16"})


class _Altered:
    def __init__(self, pending, alter, n_live: int):
        self._pending, self._alter, self._n = pending, alter, n_live

    def result(self):
        ids, dists = self._pending.result()
        return self._alter(np.array(ids), np.array(dists), self._n)


def faulty_engine(fault: str):
    """A `VectorEngine` subclass whose batches come back with `fault`."""
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; have {FAULTS}")

    class Faulty(VectorEngine):
        _last = None

        def serve_batch_nowait(self, vectors, params=None, *, n_live=None):
            pending = super().serve_batch_nowait(vectors, params,
                                                 n_live=n_live)
            n = vectors.shape[0] if n_live is None else n_live
            return _Altered(pending, self._alter, n)

        def _alter(self, ids, dists, b):
            if fault == "stale":
                prev, self._last = self._last, (ids, dists)
                return prev if prev is not None else (ids, dists)
            if fault == "half_batch":
                h = max(b // 2, 1)
                ids[h:b], dists[h:b] = ids[:b - h], dists[:b - h]
            elif fault == "alter_answer":
                ids[:, 0] = (ids[:, 0] + 1) % self.index.n
            elif fault == "misroute":
                ids[:b], dists[:b] = (np.roll(ids[:b], 1, axis=0),
                                      np.roll(dists[:b], 1, axis=0))
            return ids, dists

    Faulty.__name__ = f"Faulty_{fault}"
    return Faulty
