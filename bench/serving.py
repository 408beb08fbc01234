"""The system under test as the benchmark stands it up: an `LCCSIndex`
built from the corpus, served by `RetrievalEngine` replicas behind one
`Router`.

ANN users submit vectors, not tokens, so `VectorEngine` overrides only
`RetrievalEngine.embed` (a cast to float32) and runs no backbone.  The
replicas share one index object, as `Router.replicate` shares it; the list
is built here because `replicate` clones the base class, whose `embed`
runs the language-model backbone.
"""
from __future__ import annotations

import shutil
from pathlib import Path

import jax.numpy as jnp

from repro.core import LCCSIndex, SearchParams
from repro.router import Router
from repro.serve import RetrievalEngine


class VectorEngine(RetrievalEngine):
    """A retrieval engine whose queries arrive as vectors."""

    def __init__(self, **kw):
        super().__init__(None, None, **kw)

    def embed(self, vectors):
        return jnp.asarray(vectors, jnp.float32)


def search_params(config: dict, traffic: dict) -> SearchParams:
    return SearchParams(k=int(config["k"]), store=config["store"],
                        **traffic["search"])


TAILS = ("device", "disk")
TAIL_ROOM = 1.1  # free space a tail file needs, as a multiple of its bytes


def tail_of(config: dict) -> str:
    """Where the configuration keeps its fp32 rerank rows: "device" or
    "disk".  A disk tail needs an inexact store (fp32 has no rerank)."""
    tail = config.get("tail", "device")
    if tail not in TAILS:
        raise ValueError(f"tail must be one of {TAILS}, got {tail!r}")
    if tail == "disk" and config["store"] == "fp32":
        raise ValueError("tail 'disk' needs an inexact store; an fp32 store "
                         "verifies in one stage and has no rerank tail")
    return tail


def ensure_room(directory, nbytes: int) -> None:
    """Exit non-zero unless `directory` has room for an `nbytes` tail file:
    a deployment whose tail does not fit is not served from the device in
    its place, since that is another layout."""
    need = int(TAIL_ROOM * nbytes)
    free = shutil.disk_usage(directory).free
    if free < need:
        raise SystemExit(f"[bench] error: the tail needs {need} bytes free "
                         f"under {directory}; it has {free}")


def build_index(X, config: dict, w: float, seed: int,
                tail_dir: str | Path | None = None):
    """`LCCSIndex.build` as a user calls it for this deployment; a disk
    tail is written to `tail_dir`."""
    tail_path = None
    if tail_of(config) == "disk":
        if tail_dir is None:
            raise ValueError("a disk tail needs a directory to live in")
        ensure_room(tail_dir, X.size * 4)
        tail_path = Path(tail_dir) / "tail.npy"
    return LCCSIndex.build(X, m=int(config["m"]), family=config["family"],
                           w=w, seed=seed, store=config["store"],
                           tail_path=tail_path)


def make_router(index, config: dict, traffic: dict, *,
                engine_cls=VectorEngine) -> Router:
    params = search_params(config, traffic)
    engines = []
    for i in range(int(traffic["replicas"])):
        e = engine_cls(m=int(config["m"]), metric=config["metric"],
                       max_batch=int(traffic["max_batch"]),
                       search_params=params, store=config["store"],
                       name=f"replica-{i}")
        e.index = index
        engines.append(e)
    return Router(engines, params=params,
                  max_depth=int(traffic["max_depth"]),
                  default_slo_ms=float(traffic["slo_ms"]),
                  linger_ms=float(traffic["linger_ms"]))
