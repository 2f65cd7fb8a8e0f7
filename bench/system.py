"""Glue between the benchmark's data files and the program's entry points:
the configuration file as the program's ``TwoTowerConfig``, the search
settings of a cell, and the served-answer check every cell shares."""
from __future__ import annotations

import numpy as np

from bench.reference import ivfpq


def model_dict(cfg: dict) -> dict:
    """The model sizes of a configuration file (what the reference reads)."""
    keys = ("item_vocab", "embed_dim", "tower_dims", "hist_len",
            "hinge_margin", "index")
    return {k: cfg[k] for k in keys}


def twotower_config(cfg: dict):
    """The program's ``TwoTowerConfig`` for a configuration file."""
    import jax.numpy as jnp

    from repro.core.index_layer import IndexLayerConfig
    from repro.models.recsys import TwoTowerConfig

    ix = cfg["index"]
    return TwoTowerConfig(
        name=cfg["name"], item_vocab=cfg["item_vocab"],
        embed_dim=cfg["embed_dim"], tower_dims=tuple(cfg["tower_dims"]),
        hist_len=cfg["hist_len"], scoring=cfg["scoring"],
        hinge_margin=cfg["hinge_margin"],
        index=IndexLayerConfig(dim=ix["dim"],
                               num_subspaces=ix["num_subspaces"],
                               num_codewords=ix["num_codewords"],
                               distortion_weight=ix["distortion_weight"]),
        dtype=jnp.float32, param_dtype=jnp.float32)


def register_arch(model) -> str:
    """Register a configuration with the program's arch registry (the way
    the trainer's entry point finds it) and return its id."""
    from repro import configs
    from repro.configs import base

    arch_id = "bench-" + model.name
    configs.REGISTRY[arch_id] = base.ArchSpec(
        arch_id=arch_id, family="recsys", make_config=lambda: model,
        make_smoke=lambda: model, shapes=base.RECSYS_SHAPES)
    return arch_id


def search_config(cfg: dict, wl: dict):
    from repro import search

    ix = cfg["index"]
    return search.SearchConfig(
        subspaces=ix["num_subspaces"], codewords=ix["num_codewords"],
        num_lists=wl["lists"], nprobe=wl["nprobe"], block_size=128,
        train_size=wl["train_size"], fused_refresh=True)


def serving_index(run, wl: dict, cfg: dict):
    """(searcher, state, corpus key) of a serving cell: the configuration's
    corpus (``bench/corpus.py``), built into an IVF-PQ index through the
    program's searcher. Serving cells hold no trained rotation; the index
    is built at R = I.

    The corpus and the build come from the workload's ``corpus_seed``, not
    from the run's seed: a deployment serves one catalog, whose list sizes
    fix the shapes the Engine compiles and the work each query costs, and
    every run of the cell measures that same work. The run's seed draws
    the queries and their order."""
    import jax
    import jax.numpy as jnp

    from bench import corpus
    from repro import search

    key = jax.random.PRNGKey(wl["corpus_seed"])
    ckey = jax.random.fold_in(key, 1)
    n = cfg["index"]["dim"]
    X = corpus.corpus(ckey, num=cfg["item_vocab"], dim=n, **wl["corpus"])
    searcher = search.make("ivf")
    state = searcher.build(jax.random.fold_in(key, 7), X,
                           jnp.eye(n, dtype=jnp.float32),
                           search_config(cfg, wl))
    del X
    return searcher, state, ckey


def query_pool(run, wl: dict, cfg: dict, ckey, num: int, salt: int):
    """(num, dim) host float32 queries from the corpus's mixture."""
    import jax

    from bench import corpus

    key = jax.random.fold_in(jax.random.PRNGKey(run.seed), salt)
    return np.asarray(corpus.queries(key, ckey, num=num,
                                     dim=cfg["index"]["dim"],
                                     **wl["corpus"]))


def served_check(state, Q, scores, ids, *, nprobe: int, k: int,
                 limits: dict, qdelta=None) -> dict:
    """The numbers of ``reference/ivfpq.py compare`` that the cell's
    ``limits`` hold (a number without a limit there is not compared),
    against the plain float32 search over the same build-time index, with
    the reference's own query transform ``qdelta`` (None: as built)."""
    from bench import harness

    index = ivfpq.Index.from_state(state, qdelta)
    got = ivfpq.compare(index, np.asarray(Q), np.asarray(scores),
                        np.asarray(ids), nprobe=nprobe, k=k)
    return {name: harness.check(got[name], limits[name])
            for name in got if name in limits}
