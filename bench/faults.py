"""The precision control and the planted faults that `correct` must catch.

None of these runs in a benchmark run.  `bench/control.py` reads them on
the chip, at a cell's own size, to set the limits in `bench/cells/`; the
CPU tests in `bench/tests/` check that each one turns `correct` false.

control       the program's own lower-precision path in place of the
              configured one: the fp32 store becomes a bf16 store, so
              verify ranks and reports distances on bf16 rows.  An
              inexact store (int8, bf16) keeps its store, and its fp32
              rerank rows are rounded to bf16: in place for a device tail,
              as a second tail file beside the first for a disk tail.  So
              the rerank reports distances on bf16 rows.
stale         a batch returns the previous batch's answers: a step that
              returns its state unchanged.
half_batch    the second half of each batch gets the first half's answers.
alter_answer  every answer's nearest id is moved to the next corpus row.
misroute      each request gets the answer of the next one in its batch.
"""
from __future__ import annotations

import dataclasses
from pathlib import Path

import jax.numpy as jnp
import numpy as np

from repro.exec.topology import has_disk_tail
from repro.store import TailWriter, make_store

from .serving import VectorEngine, ensure_room

FAULTS = ("stale", "half_batch", "alter_answer", "misroute")
VARIANTS = ("sound", "control") + FAULTS


# rows rounded per block for a disk tail: rounding the whole corpus at once
# would raise the device's peak by its bf16 and fp32 copies
_ROUND_ROWS = 65536


def _bf16(rows):
    return rows.astype(jnp.bfloat16).astype(jnp.float32)


def control_index(index, X, config: dict):
    """(index, config) for the precision control of a built index `X` was
    built from: an fp32 store is served as bf16; an inexact store's fp32
    rerank rows are rounded to bf16."""
    if config["store"] == "fp32":
        return (dataclasses.replace(index, store=make_store("bf16", X)),
                {**config, "store": "bf16"})
    if not has_disk_tail(index):
        return dataclasses.replace(index, tail=_bf16(index.tail)), config
    directory = Path(index.tail_path).parent
    ensure_room(directory, X.size * 4)
    writer = TailWriter(directory / "control-bf16.npy", X.shape[1])
    for lo in range(0, X.shape[0], _ROUND_ROWS):
        writer.append(np.asarray(_bf16(X[lo:lo + _ROUND_ROWS])))
    return dataclasses.replace(index, tail_path=writer.finalize()), config


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
