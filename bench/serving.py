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


def build_index(X, config: dict, w: float, seed: int):
    """`LCCSIndex.build` as a user calls it for this deployment."""
    return LCCSIndex.build(X, m=int(config["m"]), family=config["family"],
                           w=w, seed=seed, store=config["store"])


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
