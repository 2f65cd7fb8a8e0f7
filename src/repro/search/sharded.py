"""Row-sharded searcher family: the corpus partitioned over the device mesh.

The replicated backends cap corpus size at one chip's HBM and throughput at
one chip's bandwidth. This module is the distributed half of the registry —
every replicated backend gets a ``*_sharded`` twin that keeps the paper's
serving transform and the SearchResult contract while the corpus lives
partitioned over the mesh's "data" axis end to end (the GPU-scale ANN
recipe of Wieschollek et al.: partition the database, search partitions in
parallel, merge per-partition top-k):

  exact_sharded    per-shard tiled brute-force scan over local rows
  flat_sharded     per-shard flat ADC scan over the local CSR codes
  ivf_sharded      per-shard probe + fused selected-block scan — every
                   device probes the same top-``nprobe`` lists of the
                   SHARED coarse quantizer but scans only its local lists

All three run the existing single-device scan as the shard-local body of a
``jax.shard_map``: per-shard arrays (rotated corpus / CSR codes, ids,
list offsets) are stacked on a leading shard axis and partitioned with the
``ivf_sharded`` rule table (sharding/rules.py — corpus rows over
("pod", "data")), while R, the coarse centroids, and the residual
codebooks stay replicated (O(n²) vs O(N) state). Each shard emits a padded
local top-k honoring the −inf/−1 contract — including when k exceeds its
local pool — and the static-shape merge is an ``all_gather`` of the
(b, k) runs + re-top-k (``kernels.ops.topk_merge``), so the collective
payload is O(b·k·shards), independent of corpus size.

Parity: built (or ``attach``-ed) from the same artifacts, a sharded
backend returns bit-identical scores to its replicated twin — per-row
scores are computed by the same kernel on the same codes, and the merge
only reorders candidates (tests/test_distributed.py pins all three on an
8-fake-device mesh). ``refresh`` broadcasts the (small, replicated)
RotationDelta and updates R/coarse/codebooks in place — per-shard CSR
state, pytree structure, and statics are untouched, so a live rotation
refresh costs zero recompiles and zero cross-device traffic
(``maintain.rotate_components``).

The registry serves them like any other backend::

    mesh = launch.mesh.make_data_mesh()            # ("data",) over all devices
    searcher = search.make("ivf_sharded", mesh=mesh)
    state = searcher.build(key, corpus, R, cfg)    # corpus rows partitioned
    engine = search.Engine(searcher, state, k=10, nprobe=16)

and ``search.Engine`` needs no changes: the LUT cache keys on replicated
quantities, the compile cache on (bucket, k, nprobe), and chunked/ragged
batches flow through the shard_map'd executables unchanged.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import ClassVar

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import compat, obs, quant, rotations
from repro.churn import buffer as churn_buffer
from repro.index import ivf as index_ivf
from repro.index import maintain
from repro.index import search as index_search
from repro.index.ivf import IVFPQIndex
from repro.kernels import ops as kops
from repro.kernels.common import use_kernels
from repro.search import exact as exact_mod
from repro.search import flat as flat_mod
from repro.search.base import SearchConfig, SearchResult, topk_padded
from repro.sharding import rules as sh


AxisSpec = str | tuple[str, ...]


def resolve_mesh(mesh: Mesh | None = None,
                 axis: AxisSpec = "auto") -> Mesh:
    """The serving mesh: an explicit one, else the ambient mesh context (if
    it has a shard axis), else a fresh 1-axis mesh over every device.

    The ambient mesh must be a concrete ``Mesh`` — shard placement needs
    real devices, and the ``jax.set_mesh`` context reports an
    AbstractMesh (no device list), which cannot place index shards.
    """
    if mesh is not None:
        return mesh
    ambient = compat.current_mesh()
    if (isinstance(ambient, Mesh)
            and getattr(ambient, "devices", None) is not None):
        try:
            resolve_axes(ambient, axis)
            return ambient
        except ValueError:
            pass
    from repro.launch.mesh import make_data_mesh

    return make_data_mesh()


def resolve_axes(mesh: Mesh, axis: AxisSpec = "auto") -> tuple[str, ...]:
    """The mesh axes the corpus rows shard over.

    ``"auto"`` takes the row-sharded rule table's axes present on this
    mesh (``IVF_SHARDED_RULES["ivf_rows"] == ("pod", "data")`` → both on a
    multi-pod mesh, just ``("data",)`` on a data-only one), so the shard
    count is the FULL product of the row axes — a (2, 16) pod×data mesh
    shards 32 ways, it does not silently replicate over "pod"."""
    if axis == "auto" or axis is None:
        rule = sh.IVF_SHARDED_RULES["ivf_rows"]
        kept = tuple(a for a in rule if a in mesh.shape)
        if not kept:
            raise ValueError(
                f"mesh {dict(mesh.shape)} has none of the row-shard axes "
                f"{rule}")
        return kept
    axes = (axis,) if isinstance(axis, str) else tuple(axis)
    for a in axes:
        if a not in mesh.shape:
            raise ValueError(
                f"mesh {dict(mesh.shape)} has no {a!r} axis to shard over")
    return axes


def _num_shards(mesh: Mesh, axes: tuple[str, ...]) -> int:
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def _place_sharded(arr: jax.Array, mesh: Mesh,
                   axes: tuple[str, ...]) -> jax.Array:
    """Partition a stacked (S, ...) per-shard array over the mesh: leading
    (shard) axis over the resolved row axes — the placement half of the
    ``ivf_sharded`` rule table, with S = the axis-size product by
    construction so the spec never silently drops to replication."""
    spec = P(axes if len(axes) > 1 else axes[0],
             *([None] * (arr.ndim - 1)))
    return jax.device_put(arr, NamedSharding(mesh, spec))


def _replicated_specs(tree) -> object:
    """A matching tree of replicated PartitionSpecs for a pytree argument."""
    return jax.tree.map(lambda _: P(), tree)


def _shard_spec(axes: tuple[str, ...]) -> P:
    """in_spec for a stacked (S, ...) per-shard array: leading dim over the
    resolved row axes."""
    return P(axes if len(axes) > 1 else axes[0])


def _merge_local_topk(scores: jax.Array, ids: jax.Array, k: int,
                      axes: tuple[str, ...]) -> tuple[jax.Array, jax.Array]:
    """Inside shard_map: concatenate every shard's padded (b, k) run and
    re-top-k. Static shapes — (b, S·k) — whatever the per-shard pools.
    The ``jax.named_scope`` labels the gather+merge stage in the HLO, so an
    XLA profile (``jax.profiler.trace``) separates collective time from
    scan time at zero runtime cost."""
    with jax.named_scope("obs.gather_merge"):
        g_scores = jax.lax.all_gather(scores, axes, axis=1, tiled=True)
        g_ids = jax.lax.all_gather(ids, axes, axis=1, tiled=True)
        return kops.topk_merge(g_scores, g_ids, k)


def _record_shard_gauges(backend: str, ids: np.ndarray) -> None:
    """Per-shard row gauges + the imbalance gauge on the global registry
    (``ids`` is the stacked (S, rows_s) id array, −1 = hole/padding). Host
    data is already in hand at build/attach time, so this costs nothing on
    the query path; gated on ``obs.enabled()`` by the callers."""
    reg = obs.default_registry()
    rows = (np.asarray(ids) >= 0).sum(axis=1)
    for s, r in enumerate(rows.tolist()):
        reg.gauge("index.shard_rows", backend=backend, shard=s).set(r)
    imbalance = float(rows.max()) / max(float(rows.mean()), 1.0)
    reg.gauge("index.shard_imbalance", backend=backend).set(imbalance)
    reg.event("shard_layout", backend=backend, shards=int(rows.size),
              rows=[int(r) for r in rows], imbalance=imbalance)


def _shard_rows_stats(ids: np.ndarray) -> dict:
    """The per-shard occupancy facts every sharded ``stats()`` reports."""
    rows = (np.asarray(ids) >= 0).sum(axis=1)
    return dict(
        rows_per_shard=[int(r) for r in rows],
        shard_imbalance=float(rows.max()) / max(float(rows.mean()), 1.0),
    )


# ---------------------------------------------------------------------------
# exact_sharded
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ShardedExactState:
    """Rotated corpus stacked per shard; R replicated. ``mesh``/``axes``
    are static aux data, so jit specializes per mesh layout and a refresh
    (same shapes, same statics) never invalidates a compiled executable."""

    R: jax.Array        # (n, n) serving rotation, replicated
    XR: jax.Array       # (S, rows_s, n) rotated corpus, zero-padded
    ids: jax.Array      # (S, rows_s) int32 global item ids, −1 = padding
    mesh: Mesh = dataclasses.field(metadata={"static": True})
    tile_rows: int = dataclasses.field(default=4096,
                                       metadata={"static": True})
    axes: tuple[str, ...] = dataclasses.field(default=("data",),
                                              metadata={"static": True})
    R0: jax.Array | None = None  # frozen build rotation (fused refresh)


@functools.partial(jax.jit, static_argnames=("k",))
def _exact_sharded_search(state: ShardedExactState, Q: jax.Array,
                          k: int) -> SearchResult:
    axes = state.axes
    # fused mode scores with the frozen R₀ (delta cancels against the frozen
    # shards — see search/exact.py); resolved here so the shard-local body
    # is mode-agnostic
    Rq = exact_mod._query_rotation(state)

    def local(R, XR_s, ids_s, Q):
        lstate = exact_mod.ExactState(R=R, XR=XR_s[0], ids=ids_s[0],
                                      tile_rows=state.tile_rows)
        with jax.named_scope("obs.shard_scan"):
            res = exact_mod._exact_search_impl(lstate, Q, k)
        scores, ids = _merge_local_topk(res.scores, res.ids, k, axes)
        return SearchResult(scores=scores, ids=ids,
                            scanned=jax.lax.psum(res.scanned, axes))

    f = jax.shard_map(
        local, mesh=state.mesh,
        in_specs=(P(), _shard_spec(axes), _shard_spec(axes), P()),
        out_specs=SearchResult(scores=P(), ids=P(), scanned=P()),
        check_vma=False,
    )
    return f(Rq, state.XR, state.ids, Q)


@dataclasses.dataclass(frozen=True)
class ExactSharded:
    """Registry backend ``"exact_sharded"`` (see module docstring)."""

    name: ClassVar[str] = "exact_sharded"
    mesh: Mesh | None = None
    axis: AxisSpec = "auto"

    def build(self, key: jax.Array, corpus: jax.Array, R: jax.Array,
              cfg: SearchConfig) -> ShardedExactState:
        del key  # deterministic build
        mesh = resolve_mesh(self.mesh, self.axis)
        axes = resolve_axes(mesh, self.axis)
        S = _num_shards(mesh, axes)
        R = jnp.asarray(R)
        XR = jnp.asarray(corpus) @ R.astype(corpus.dtype)
        n_rows, n = XR.shape
        rows_s = max(-(-n_rows // S), 1)
        tile = max(1, min(cfg.tile_rows, rows_s))
        rows_s = -(-rows_s // tile) * tile          # whole tiles per shard
        pad = rows_s * S - n_rows
        ids = jnp.concatenate([
            jnp.arange(n_rows, dtype=jnp.int32),
            jnp.full((pad,), -1, jnp.int32),
        ]).reshape(S, rows_s)
        XR = jnp.pad(XR, ((0, pad), (0, 0))).reshape(S, rows_s, n)
        if obs.enabled():
            _record_shard_gauges(self.name, np.asarray(ids))
        return ShardedExactState(
            R=R, XR=_place_sharded(XR, mesh, axes),
            ids=_place_sharded(ids, mesh, axes),
            mesh=mesh, tile_rows=tile, axes=axes,
            R0=R if cfg.fused_refresh else None)

    def search(self, state: ShardedExactState, Q: jax.Array, *,
               k: int = 10) -> SearchResult:
        return _exact_sharded_search(state, Q, k)

    def refresh(self, state: ShardedExactState,
                delta: rotations.RotationDelta) -> ShardedExactState:
        if state.R0 is not None:
            # fused: the frozen shards cancel the delta exactly — no
            # cross-device XR re-materialization, only R tracks the trainer
            return dataclasses.replace(
                state, R=rotations.apply(state.R, delta))
        return dataclasses.replace(
            state,
            R=rotations.apply(state.R, delta),
            XR=rotations.apply(state.XR, delta),
        )

    def stats(self, state: ShardedExactState) -> dict:
        ids = np.asarray(state.ids)
        rows = int(np.sum(ids >= 0))
        S = ids.shape[0]
        return dict(
            backend=self.name,
            rows=rows,
            capacity=int(ids.size),
            dim=int(state.XR.shape[-1]),
            shards=S,
            tile_rows=state.tile_rows,
            scan_rows_per_query=rows,
            scan_rows_per_query_per_device=rows / S,
            memory_bytes=int(state.XR.size * state.XR.dtype.itemsize),
            memory_bytes_per_device=int(
                state.XR.size * state.XR.dtype.itemsize) // S,
            compression=1.0,
            fused_refresh=state.R0 is not None,
            **_shard_rows_stats(ids),
        )


# ---------------------------------------------------------------------------
# flat_sharded / ivf_sharded — the quantized family over stacked CSR shards
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ShardedADCState:
    """Quantized sharded state: shared quantizers + stacked per-shard CSRs.

    R/coarse/quantizer are the replicated O(n²) components a refresh
    rotates; codes/ids/list_offsets hold one block-aligned CSR per shard
    (padded to a common capacity with hole rows so they stack). ``nprobe``
    and ``max_blocks`` (the MAX over shards' longest lists — every shard
    runs the same program) mirror ``ADCState``'s statics.
    """

    R: jax.Array              # (n, n) replicated
    coarse: quant.VQ          # shared coarse quantizer (L centroids)
    quantizer: quant.Quantizer  # shared residual quantizer
    codes: jax.Array          # (S, cap_s, Dp) per-shard CSR codes
    ids: jax.Array            # (S, cap_s) int32 global ids, −1 = hole
    list_offsets: jax.Array   # (S, L+1) per-shard list offsets
    mesh: Mesh = dataclasses.field(metadata={"static": True})
    block_size: int = dataclasses.field(default=128,
                                        metadata={"static": True})
    nprobe: int = dataclasses.field(default=8, metadata={"static": True})
    max_blocks: int = dataclasses.field(default=-1,
                                        metadata={"static": True})
    use_kernel: bool | None = dataclasses.field(default=None,
                                                metadata={"static": True})
    axes: tuple[str, ...] = dataclasses.field(default=("data",),
                                              metadata={"static": True})
    lut_dtype: str = dataclasses.field(default="float32",
                                       metadata={"static": True})
    rot: jax.Array | None = None     # fused refresh: live rotation R₀·Δ
    wacc: jax.Array | None = None    # fused refresh: within-subspace W
    qdelta: jax.Array | None = None  # fused refresh: query transform Δ·Wᵀ
    # live-churn append buffers, one per shard stacked on the leading axis
    # and partitioned like the CSR; each shard's side pass runs inside the
    # shard_map local body (repro.churn). None until churn.with_staging.
    staging: churn_buffer.StagingBuffer | None = None

    @property
    def num_shards(self) -> int:
        return self.codes.shape[0]

    @property
    def num_lists(self) -> int:
        return self.list_offsets.shape[1] - 1


def _fused_sharded_state(state: ShardedADCState) -> ShardedADCState:
    """Initialize the fused-refresh matrices at the build rotation
    (Δ = W = I: rot = R₀, qdelta = I — mirrors ``flat._fused_state``)."""
    n = state.R.shape[0]
    eye = jnp.eye(n, dtype=state.R.dtype)
    return dataclasses.replace(state, rot=state.R, wacc=eye, qdelta=eye)


def attach_shards(parts: list[IVFPQIndex], *, mesh: Mesh | None = None,
                  axis: AxisSpec = "auto", nprobe: int = 8,
                  use_kernel: bool | None = None, lut_dtype: str = "float32",
                  fused_refresh: bool = False) -> ShardedADCState:
    """Stack per-shard indexes (``ivf.shard_split`` or ``ivf.build_sharded``
    output) into one servable sharded state.

    All parts must share R / coarse / quantizer / block_size — checked
    below, because serving decodes every shard against shard 0's
    quantizers and a mismatch would be silently wrong, not loud. Shorter
    shards pad to the max capacity with hole rows appended AFTER their
    sentinel block — unreferenced by any offset, id −1, so both the flat
    scan (masked) and the probe scan (never scheduled) ignore them.

    Assembly is host-side (one stacked array per field before placement),
    so the attach step needs the whole index in host memory even though
    serving state is partitioned — fine up to host RAM (codes are the
    compressed 2 B-ish/row payload, not the f32 corpus). Past that, feed
    per-shard chunks through ``ivf.build_sharded`` so no step ever holds
    more than a chunk, and on a real multi-host pod attach per-host
    shard lists (single-host process assumption here matches the repo's
    forced-host-device test rig).
    """
    mesh = resolve_mesh(mesh, axis)
    axes = resolve_axes(mesh, axis)
    S = _num_shards(mesh, axes)
    if len(parts) != S:
        raise ValueError(
            f"{len(parts)} index shards for a {S}-way {axes!r} mesh axis")
    head = parts[0]
    # the shared components must be IDENTICAL across shards — serving
    # decodes every shard's codes against shard 0's quantizers, so a list
    # of independently-fit per-chunk indexes would return well-formed but
    # silently wrong scores. Fail loudly instead (use ``shard_split`` or
    # ``build_sharded``, which share one fit by construction).
    for i, p in enumerate(parts[1:], start=1):
        if (p.block_size != head.block_size
                or not np.array_equal(np.asarray(p.R), np.asarray(head.R))
                or not np.array_equal(np.asarray(p.coarse.centroids),
                                      np.asarray(head.coarse.centroids))
                or not np.array_equal(np.asarray(p.quantizer.codebooks),
                                      np.asarray(head.quantizer.codebooks))):
            raise ValueError(
                f"index shard {i} does not share shard 0's R/coarse/"
                "quantizer/block_size — sharded serving requires one fit "
                "across all shards (ivf.shard_split / ivf.build_sharded)")
    cap = max(p.capacity for p in parts)
    codes, ids = [], []
    for p in parts:
        extra = cap - p.capacity
        codes.append(np.pad(np.asarray(p.codes), ((0, extra), (0, 0))))
        ids.append(np.pad(np.asarray(p.ids), (0, extra),
                          constant_values=-1))
    if obs.enabled():
        # one ShardedADCState serves both flat_sharded and ivf_sharded
        _record_shard_gauges("adc_sharded", np.stack(ids))
    state = ShardedADCState(
        R=head.R, coarse=head.coarse, quantizer=head.quantizer,
        codes=_place_sharded(jnp.asarray(np.stack(codes)), mesh, axes),
        ids=_place_sharded(jnp.asarray(np.stack(ids)), mesh, axes),
        list_offsets=_place_sharded(
            jnp.asarray(np.stack([np.asarray(p.list_offsets)
                                  for p in parts])), mesh, axes),
        mesh=mesh, block_size=head.block_size,
        nprobe=min(nprobe, head.num_lists),
        max_blocks=max(max(p.max_list_blocks() for p in parts), 1),
        use_kernel=use_kernels(use_kernel), axes=axes, lut_dtype=lut_dtype,
    )
    return _fused_sharded_state(state) if fused_refresh else state


def _local_index(R, coarse, quantizer, codes_s, ids_s, offs_s,
                 block_size: int) -> IVFPQIndex:
    """This shard's single-device index view (inside shard_map: the leading
    shard axis arrives as a size-1 block)."""
    return IVFPQIndex(R=R, coarse=coarse, quantizer=quantizer,
                      codes=codes_s[0], ids=ids_s[0],
                      list_offsets=offs_s[0], block_size=block_size)


def _sharded_scan(state: ShardedADCState, QR: jax.Array, lut,
                  local_body):
    """Run ``local_body(local_index, QR, lut) -> SearchResult`` on every
    shard and merge (body already emits a padded local top-k). With a
    staging buffer attached, each shard's staged rows ride its local
    flat-ADC side pass and fold into its run before the cross-shard merge
    — staged rows never cross devices."""
    axes = state.axes
    stg = state.staging
    extra = () if stg is None else (stg.codes, stg.ids, stg.lists)
    extra_specs = () if stg is None else (_shard_spec(axes),) * 3

    def local(R, coarse, quantizer, codes, ids, offs, QR, lut, *stg_parts):
        idx = _local_index(R, coarse, quantizer, codes, ids, offs,
                           state.block_size)
        with jax.named_scope("obs.shard_scan"):
            res = local_body(idx, QR, lut)
            if stg_parts:
                buf = churn_buffer.StagingBuffer(
                    codes=stg_parts[0][0], ids=stg_parts[1][0],
                    lists=stg_parts[2][0])
                res = churn_buffer.merge_staged(
                    res, buf, QR, lut, coarse.centroids,
                    res.scores.shape[1], use_kernel=state.use_kernel)
        scores, out_ids = _merge_local_topk(
            res.scores, res.ids, res.scores.shape[1], axes)
        return SearchResult(scores=scores, ids=out_ids,
                            scanned=jax.lax.psum(res.scanned, axes))

    f = jax.shard_map(
        local, mesh=state.mesh,
        in_specs=(P(), _replicated_specs(state.coarse),
                  _replicated_specs(state.quantizer),
                  _shard_spec(axes), _shard_spec(axes), _shard_spec(axes),
                  P(), _replicated_specs(lut), *extra_specs),
        out_specs=SearchResult(scores=P(), ids=P(), scanned=P()),
        check_vma=False,
    )
    return f(state.R, state.coarse, state.quantizer, state.codes, state.ids,
             state.list_offsets, QR, lut, *extra)


def _flat_local_body(k: int, use_kernel: bool):
    def body(idx: IVFPQIndex, QR, lut) -> SearchResult:
        scores, cand_ids = index_search.flat_adc_prepared(
            idx, QR, lut, use_kernel=use_kernel)
        top_scores, top_ids = topk_padded(scores, cand_ids, k)
        scanned = jnp.full((QR.shape[0],), idx.capacity, jnp.int32)
        return SearchResult(scores=top_scores, ids=top_ids, scanned=scanned)

    return body


def _ivf_local_body(k: int, nprobe: int, max_blocks: int, use_kernel: bool):
    def body(idx: IVFPQIndex, QR, lut) -> SearchResult:
        # every shard probes the same lists of the shared coarse quantizer
        # (the probe is replicated work, O(b·L)) but scans only its local
        # CSR blocks — the O(rows) term is what divides by the shard count
        return index_search._search_core(
            idx, QR, lut, nprobe=nprobe, k=k, max_blocks=max_blocks,
            use_kernel=use_kernel)

    return body


@functools.partial(jax.jit, static_argnames=("k",))
def _flat_sharded_prepared(state: ShardedADCState, QR: jax.Array,
                           lut, k: int) -> SearchResult:
    return _sharded_scan(state, QR, lut,
                         _flat_local_body(k, state.use_kernel))


@functools.partial(jax.jit, static_argnames=("k", "nprobe"))
def _ivf_sharded_prepared(state: ShardedADCState, QR: jax.Array,
                          lut, k: int,
                          nprobe: int) -> SearchResult:
    return _sharded_scan(
        state, QR, lut,
        _ivf_local_body(k, nprobe, state.max_blocks, state.use_kernel))


def _sharded_refresh(state: ShardedADCState,
                     delta: rotations.RotationDelta) -> ShardedADCState:
    """Broadcast the (small, replicated) delta: rotate R/coarse/codebooks
    in place, leave every shard's CSR untouched — structure and statics are
    refresh-invariant, so compiled executables survive. In fused mode even
    R/coarse/codebooks are frozen and only the three query-side matrices
    advance (see ``flat._fused_refresh_mats``)."""
    maintain.check_refreshable(delta)
    if state.rot is not None:
        rot, wacc, qdelta = flat_mod._fused_refresh_mats(
            state.R, state.rot, state.wacc,
            delta.pi, delta.pj, delta.theta, state.quantizer.sub)
        return dataclasses.replace(state, rot=rot, wacc=wacc, qdelta=qdelta)
    R, coarse, quantizer = maintain.rotate_components(
        state.R, state.coarse, state.quantizer,
        delta.pi, delta.pj, delta.theta)
    return dataclasses.replace(state, R=R, coarse=coarse,
                               quantizer=quantizer)


def _sharded_luts_refresh_invariant(state: ShardedADCState,
                                    delta: rotations.RotationDelta) -> bool:
    """Sharded twin of ``flat._luts_refresh_invariant`` — same criterion
    (fused mode + purely within-subspace disjoint GivensDelta), reading the
    shared quantizer directly off the sharded state."""
    if state.rot is None:
        return False
    if not isinstance(delta, rotations.GivensDelta) or delta.overlapping:
        return False
    sub = state.quantizer.sub
    pi = np.asarray(delta.pi)
    pj = np.asarray(delta.pj)
    return bool(np.all((pi // sub) == (pj // sub)))


def _sharded_adc_stats(name: str, state: ShardedADCState) -> dict:
    ids = np.asarray(state.ids)
    live = int(np.sum(ids >= 0))
    S = state.num_shards
    code_bytes = int(state.codes.shape[-1] * state.codes.dtype.itemsize)
    mem = int(state.codes.size * state.codes.dtype.itemsize)
    return dict(
        backend=name,
        rows=live,
        capacity=int(ids.size),
        dim=int(state.coarse.dim),
        shards=S,
        num_lists=state.num_lists,
        code_bytes_per_row=code_bytes,
        compression=float(state.coarse.dim * 4 / code_bytes),
        memory_bytes=mem,
        memory_bytes_per_device=mem // S,
        use_kernel=use_kernels(state.use_kernel),
        lut_dtype=state.lut_dtype,
        fused_refresh=state.rot is not None,
        **_shard_rows_stats(ids),
    )


def _shard_existing(index: IVFPQIndex, mesh: Mesh | None, axis: AxisSpec, *,
                    nprobe: int, use_kernel: bool | None,
                    lut_dtype: str = "float32",
                    fused_refresh: bool = False) -> ShardedADCState:
    mesh = resolve_mesh(mesh, axis)
    axes = resolve_axes(mesh, axis)
    parts = index_ivf.shard_split(index, _num_shards(mesh, axes))
    return attach_shards(parts, mesh=mesh, axis=axes, nprobe=nprobe,
                         use_kernel=use_kernel, lut_dtype=lut_dtype,
                         fused_refresh=fused_refresh)


# Engine LUT-cache capabilities, shared by both sharded ADC backends (the
# replicated pair shares these the same way — see flat.py):
def _rotate_queries(state: ShardedADCState, Q: jax.Array) -> jax.Array:
    # fused mode freezes R at R₀ and the coarse term is exactly invariant,
    # so Q @ state.R is the correct query rotation in both modes
    return Q @ state.R


def _luts(state: ShardedADCState, QR: jax.Array):
    """Per-query ADC LUT pack over the shared residual quantizer — fused
    LUT-build and integer quantization mirror ``flat._luts``; the pack is
    replicated, so the shard_map in_specs tree-map over it."""
    if state.qdelta is not None:
        cb_flat, colmap = state.quantizer.lut_operands()
        lut = kops.fused_lut(QR, state.qdelta, cb_flat, colmap,
                             use_kernel=use_kernels(state.use_kernel))
    else:
        lut = state.quantizer.adc_tables(QR)
    if state.lut_dtype != "float32":
        return kops.quantize_luts(lut, state.lut_dtype)
    return lut


@dataclasses.dataclass(frozen=True)
class FlatSharded:
    """Registry backend ``"flat_sharded"`` (see module docstring)."""

    name: ClassVar[str] = "flat_sharded"
    mesh: Mesh | None = None
    axis: AxisSpec = "auto"

    def build(self, key: jax.Array, corpus: jax.Array, R: jax.Array,
              cfg: SearchConfig) -> ShardedADCState:
        index = index_ivf.build(key, corpus, R, cfg.ivf_config(),
                                train_size=cfg.train_size)
        return self.attach(index, mesh=self.mesh, axis=self.axis,
                           use_kernel=cfg.use_kernel,
                           lut_dtype=cfg.lut_dtype,
                           fused_refresh=cfg.fused_refresh)

    @staticmethod
    def attach(index: IVFPQIndex, *, mesh: Mesh | None = None,
               axis: AxisSpec = "auto", nprobe: int = 8,
               use_kernel: bool | None = None, lut_dtype: str = "float32",
               fused_refresh: bool = False) -> ShardedADCState:
        """Shard an existing replicated index across the mesh — the very
        codes the single-device backends serve, redistributed (the parity
        and migration entry point)."""
        return _shard_existing(index, mesh, axis, nprobe=nprobe,
                               use_kernel=use_kernel, lut_dtype=lut_dtype,
                               fused_refresh=fused_refresh)

    def search(self, state: ShardedADCState, Q: jax.Array, *,
               k: int = 10) -> SearchResult:
        QR = _rotate_queries(state, Q)
        return _flat_sharded_prepared(state, QR, _luts(state, QR), k)

    # -- Engine LUT-cache capabilities -------------------------------------
    def rotate_queries(self, state: ShardedADCState,
                       Q: jax.Array) -> jax.Array:
        return _rotate_queries(state, Q)

    def luts(self, state: ShardedADCState, QR: jax.Array):
        return _luts(state, QR)

    def luts_refresh_invariant(self, state: ShardedADCState,
                               delta: rotations.RotationDelta) -> bool:
        return _sharded_luts_refresh_invariant(state, delta)

    def search_prepared(self, state: ShardedADCState, QR: jax.Array,
                        lut, *, k: int = 10) -> SearchResult:
        return _flat_sharded_prepared(state, QR, lut, k)

    def refresh(self, state: ShardedADCState,
                delta: rotations.RotationDelta) -> ShardedADCState:
        return _sharded_refresh(state, delta)

    def stats(self, state: ShardedADCState) -> dict:
        st = _sharded_adc_stats(self.name, state)
        st["scan_rows_per_query"] = st["capacity"]
        st["scan_rows_per_query_per_device"] = (st["capacity"]
                                                / state.num_shards)
        return st


@dataclasses.dataclass(frozen=True)
class IVFSharded:
    """Registry backend ``"ivf_sharded"`` (see module docstring)."""

    name: ClassVar[str] = "ivf_sharded"
    mesh: Mesh | None = None
    axis: AxisSpec = "auto"

    def build(self, key: jax.Array, corpus: jax.Array, R: jax.Array,
              cfg: SearchConfig) -> ShardedADCState:
        index = index_ivf.build(key, corpus, R, cfg.ivf_config(),
                                train_size=cfg.train_size)
        return self.attach(index, mesh=self.mesh, axis=self.axis,
                           nprobe=cfg.nprobe, use_kernel=cfg.use_kernel,
                           lut_dtype=cfg.lut_dtype,
                           fused_refresh=cfg.fused_refresh)

    @staticmethod
    def attach(index: IVFPQIndex, *, mesh: Mesh | None = None,
               axis: AxisSpec = "auto", nprobe: int = 8,
               use_kernel: bool | None = None, lut_dtype: str = "float32",
               fused_refresh: bool = False) -> ShardedADCState:
        """Shard an existing replicated index across the mesh (see
        ``FlatSharded.attach`` — one state serves both sharded ADC
        backends, like ``ADCState`` does for the replicated pair)."""
        return _shard_existing(index, mesh, axis, nprobe=nprobe,
                               use_kernel=use_kernel, lut_dtype=lut_dtype,
                               fused_refresh=fused_refresh)

    def effective_nprobe(self, state: ShardedADCState,
                         nprobe: int | None) -> int:
        """Engine capability: the probe width actually served (clamped at
        the shared coarse quantizer's list count)."""
        return min(state.nprobe if nprobe is None else nprobe,
                   state.num_lists)

    def scheduled_rows(self, state: ShardedADCState,
                       nprobe: int | None) -> int:
        """Engine capability: rows the scan is scheduled to read per query,
        over all shards (the replicated twin's ``scheduled_rows`` on each
        shard's window and staging buffer)."""
        rows = (self.effective_nprobe(state, nprobe) * state.max_blocks
                * state.block_size * state.num_shards)
        if state.staging is not None:
            rows += state.staging.ids.size
        return rows

    def prepare_state(self, state: ShardedADCState) -> ShardedADCState:
        """Engine capability: bake the probe window for a directly-
        constructed state (``attach_shards`` already did — one host sync
        over the stacked offsets otherwise)."""
        if state.max_blocks >= 1:
            return state
        lens = np.diff(np.asarray(state.list_offsets), axis=1)
        return dataclasses.replace(
            state, max_blocks=max(int(lens.max()) // state.block_size, 1))

    def search(self, state: ShardedADCState, Q: jax.Array, *, k: int = 10,
               nprobe: int | None = None) -> SearchResult:
        state = self.prepare_state(state)
        QR = _rotate_queries(state, Q)
        return _ivf_sharded_prepared(state, QR, _luts(state, QR), k,
                                     self.effective_nprobe(state, nprobe))

    # -- Engine LUT-cache capabilities -------------------------------------
    def rotate_queries(self, state: ShardedADCState,
                       Q: jax.Array) -> jax.Array:
        return _rotate_queries(state, Q)

    def luts(self, state: ShardedADCState, QR: jax.Array):
        return _luts(state, QR)

    def luts_refresh_invariant(self, state: ShardedADCState,
                               delta: rotations.RotationDelta) -> bool:
        return _sharded_luts_refresh_invariant(state, delta)

    def search_prepared(self, state: ShardedADCState, QR: jax.Array,
                        lut, *, k: int = 10,
                        nprobe: int | None = None) -> SearchResult:
        # prepare_state is a no-op on an attach_shards state (max_blocks
        # baked as a STATIC, concrete even under a jit trace); the host
        # sync only fires for a directly-constructed concrete state, same
        # as the replicated twin's _max_blocks fallback
        state = self.prepare_state(state)
        return _ivf_sharded_prepared(state, QR, lut, k,
                                     self.effective_nprobe(state, nprobe))

    def refresh(self, state: ShardedADCState,
                delta: rotations.RotationDelta) -> ShardedADCState:
        return _sharded_refresh(state, delta)

    def stats(self, state: ShardedADCState) -> dict:
        st = _sharded_adc_stats(self.name, state)
        st["nprobe"] = state.nprobe
        st["max_blocks"] = state.max_blocks
        per_shard = min(state.nprobe * state.max_blocks * state.block_size,
                        int(state.codes.shape[1]))
        st["scan_rows_per_query"] = per_shard * state.num_shards
        st["scan_rows_per_query_per_device"] = per_shard
        return st
