"""Multi-device execution: row-sharded scans over a jax Mesh.

The reference's only parallelism is data parallelism over row partitions
with algebraic state merge (Spark partial aggregation + shuffle;
`rdd.treeReduce` for KLL — see SURVEY.md §2.9). TPU-native equivalents here:

1. **GSPMD scan** (`sharded_update`): the fused per-batch update is jit'd
   with the feature arrays sharded over the mesh's ``rows`` axis and the
   state pytrees replicated; XLA inserts the partial-reduce + collective
   combine automatically — the analog of Spark's partial-agg + shuffle, but
   compiled, fused and riding ICI.
2. **Explicit collective merge** (`collective_merge_states`): a shard_map
   program that all-gathers per-device state pytrees over the mesh axis and
   folds them with each analyzer's semigroup ``merge`` — the
   `KLLRunner.treeReduce` analog (reference `analyzers/runners/
   KLLRunner.scala:104-112`) for states whose merge is not a plain ``psum``
   (HLL register max, KLL level concat + compaction).

Cross-host: the same code runs under multi-host jax (`jax.distributed`);
mesh axes spanning hosts make the collectives ride DCN. States serialize to
numpy pytrees (see `analyzers/state_provider.py`) for the offline/
partitioned merge path (`AnalysisRunner.run_on_aggregated_states`).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..reliability.faults import fault_point

ROW_AXIS = "rows"


def _ensure_global(tree, mesh: Mesh, specs):
    """Host arrays -> global jax.Arrays laid out per ``specs`` when the
    mesh spans PROCESSES (jax.distributed): a multi-process jit cannot
    auto-shard plain numpy inputs the way single-process jit does, so each
    process contributes its addressable shards from its (identical) host
    copy via ``make_array_from_callback``. Single-process: no-op — jit's
    own in_shardings placement is cheaper. This is what turns the
    module docstring's DCN claim into executable truth (exercised by
    ``tools/dcn_smoke.py``)."""
    if jax.process_count() == 1:
        return tree

    def convert(x, spec):
        if isinstance(x, jax.Array) and not x.is_fully_addressable:
            return x  # already a global array (e.g. a prior fold's output)
        arr = np.asarray(x)
        sharding = NamedSharding(mesh, spec)
        return jax.make_array_from_callback(
            arr.shape, sharding, lambda idx: arr[idx]
        )

    return jax.tree_util.tree_map(
        convert, tree, specs,
        is_leaf=lambda x: isinstance(x, (np.ndarray, jax.Array)),
    )


def _local_view(tree):
    """Read back a replicated-per-device result in a multi-process run:
    every device holds the identical value, so each process reads its OWN
    first addressable shard (indexing a non-addressable global array would
    throw). Single-process: identity."""
    if jax.process_count() == 1:
        return tree
    return jax.tree_util.tree_map(
        lambda x: np.asarray(x.addressable_data(0))
        if isinstance(x, jax.Array) and not x.is_fully_addressable
        else x,
        tree,
    )


def _shard_map(f, *, mesh, in_specs, out_specs):
    """`jax.shard_map` without replication checking: the merge programs
    intentionally return per-device values from replicated inputs."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )


def make_mesh(num_devices: Optional[int] = None, devices=None) -> Mesh:
    """1-D mesh over the row axis (data parallelism over row shards)."""
    if devices is None:
        devices = jax.devices()
        if num_devices is not None:
            devices = devices[:num_devices]
    return Mesh(np.asarray(devices), (ROW_AXIS,))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def row_sharded(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P(ROW_AXIS))


def shard_features(
    features: Dict[str, np.ndarray], mesh: Mesh, batch_rows: Optional[int] = None
) -> Dict[str, jax.Array]:
    """Place feature arrays row-sharded over the mesh. The batch axis is the
    one whose extent equals ``batch_rows`` (the engine pads batches to a
    multiple of the mesh size); e.g. the (2, B) HLL pairs shard on their
    LAST dim. Without ``batch_rows`` it is inferred from the 1-D arrays
    (the row mask is always present)."""
    if batch_rows is None:
        batch_rows = max(
            (a.shape[0] for a in features.values() if a.ndim == 1), default=0
        )
    out = {}
    for key, arr in features.items():
        if arr.ndim >= 1 and arr.shape[0] == batch_rows:
            spec = P(ROW_AXIS, *([None] * (arr.ndim - 1)))
        elif arr.ndim >= 2 and arr.shape[-1] == batch_rows:
            spec = P(*([None] * (arr.ndim - 1)), ROW_AXIS)
        else:
            spec = P()
        out[key] = jax.device_put(arr, NamedSharding(mesh, spec))
    return out


def sharded_update(analyzers: Sequence[Any], mesh: Mesh):
    """jit the fused update with states replicated and features row-sharded;
    XLA turns every reduction into partial-per-device + collective."""

    def fused(states: Tuple, features: Dict[str, jax.Array]) -> Tuple:
        return tuple(a.update(s, features) for a, s in zip(analyzers, states))

    return jax.jit(
        fused,
        in_shardings=(replicated(mesh), None),  # features keep their placement
        out_shardings=replicated(mesh),
        donate_argnums=0,
    )


_SHARDED_INGEST_CACHE: dict = {}

#: jitted collective-merge programs keyed by (analyzers, devices, local
#: shard count, padded leaf shapes/dtypes); bounded FIFO like the engine's
#: merge-fold cache
from ..utils import BoundedLRU

_COLLECTIVE_MERGE_CACHE = BoundedLRU(64)


def sharded_ingest_fold(
    analyzers: Sequence[Any], mesh: Mesh, states_stacked, partials_stacked, flags
):
    """Fold a chunk of host-computed partials into PER-DEVICE states over the
    mesh: the stacked partials (leading dim = n_dev * local_chunk) shard over
    the row axis, and each device lax.scans its local slice into its own
    state copy — the executor-side partial-aggregation split composed WITH
    data parallelism (reference `AnalysisRunner.scala:303-318` + Spark's
    partition parallelism). ``flags`` marks which partials are real; padding
    entries skip all analyzer work. Finish a run by merging the per-device
    states with :func:`collective_merge_states`.

    ``states_stacked``: tuple (per analyzer) of pytrees with leading n_dev
    dim. Returns the updated stacked states."""
    from ..runners.engine import _ingest_signature

    # keyed by ingest SIGNATURES, not analyzer identities: same-class/
    # same-shape batteries over different columns share one compiled
    # sharded fold (the mesh-path analog of the bundled device programs —
    # ingest_partial is a pure function of class + state/partial shapes)
    key = (
        tuple(_ingest_signature(a) for a in analyzers),
        tuple(mesh.devices.flat),
    )
    program = _SHARDED_INGEST_CACHE.get(key)
    if program is None:
        def spec_of(tree):
            # np.ndim reads the rank from metadata: no transfer either way
            return jax.tree_util.tree_map(
                lambda x: P(ROW_AXIS, *([None] * (np.ndim(x) - 1))), tree
            )

        from ..runners.engine import make_flagged_ingest_body

        body = make_flagged_ingest_body(tuple(analyzers))

        def local_fold(states, stacked, local_flags):
            local = jax.tree_util.tree_map(lambda x: x[0], states)
            out, _ = jax.lax.scan(body, local, (local_flags, stacked))
            return jax.tree_util.tree_map(lambda x: x[None], out)

        program = jax.jit(
            _shard_map(
                local_fold,
                mesh=mesh,
                in_specs=(
                    spec_of(states_stacked),
                    spec_of(partials_stacked),
                    P(ROW_AXIS),
                ),
                out_specs=spec_of(states_stacked),
            ),
            donate_argnums=0,  # states are dead after the fold, like the
            # single-device _ingest_program — no per-chunk state copies
        )
        _SHARDED_INGEST_CACHE[key] = program
    fault_point("sharded_fold")
    if jax.process_count() > 1:
        def spec_of_tree(tree):
            # np.ndim reads rank from metadata — jnp.asarray here would
            # device_put every (large) stacked leaf just to ask its rank
            return jax.tree_util.tree_map(
                lambda x: P(ROW_AXIS, *([None] * (np.ndim(x) - 1))), tree
            )

        states_stacked = _ensure_global(
            states_stacked, mesh, spec_of_tree(states_stacked)
        )
        partials_stacked = _ensure_global(
            partials_stacked, mesh, spec_of_tree(partials_stacked)
        )
        flags = _ensure_global(
            np.asarray(flags), mesh, P(ROW_AXIS)
        )
        return program(states_stacked, partials_stacked, flags)
    return program(states_stacked, partials_stacked, np.asarray(flags))


def stack_identity_states(analyzers: Sequence[Any], n_dev: int):
    """n_dev copies of each analyzer's identity state, leading dim n_dev —
    the initial per-device states for :func:`sharded_ingest_fold`. Host
    arrays: the fold's program places each row on its own device (built on
    the default device they would be copied there chip to chip)."""
    out = []
    for a in analyzers:
        ident = a.init_state()
        out.append(
            jax.tree_util.tree_map(
                lambda x: np.repeat(np.asarray(x)[None], n_dev, axis=0),
                ident,
            )
        )
    return tuple(out)


def collective_merge_states(analyzers: Sequence[Any], mesh: Mesh, per_shard_states):
    """Fold per-shard state pytrees with each analyzer's semigroup ``merge``
    in ONE collective device program — the treeReduce analog (reference
    `analyzers/runners/KLLRunner.scala:104-112`).

    ``per_shard_states`` is a tuple (one entry per analyzer) of pytrees whose
    leaves carry a leading shard dim; the shard count comes from that dim,
    NOT the mesh size, so merging e.g. 8 persisted partition states on a
    4-device mesh folds all 8.

    Execution shape (a real tree reduction, not a sequential fold):

    1. pad the shard dim to a multiple of the mesh size with identity states
       (``init_state`` — every state merge is zero-count safe) and lay the
       shards out over the mesh axis, ``k`` local shards per device;
    2. inside ``shard_map``, each device folds its ``k`` local shards;
    3. cross-device combine: a log2(n)-round **butterfly** — each round
       ``lax.ppermute``s the partial state to the XOR partner and merges, so
       every round halves the number of distinct partials and all traffic
       rides ICI (falls back to one ``all_gather`` + local fold when the
       mesh size is not a power of two).
    """
    n_dev = int(mesh.devices.size)

    def shards_of(tree) -> int:
        leaves = jax.tree_util.tree_leaves(tree)
        return int(leaves[0].shape[0]) if leaves else 0

    total = max((shards_of(t) for t in per_shard_states), default=0)
    if total == 0:
        # zero shards: the merge of an empty set is the identity state
        return tuple(a.init_state() for a in analyzers)
    k = -(-total // n_dev)  # local shards per device after padding

    # pad with identity states so the shard dim is exactly n_dev * k
    padded = []
    for a, tree in zip(analyzers, per_shard_states):
        n = shards_of(tree)
        pad = n_dev * k - n
        if pad:
            ident = a.init_state()

            def pad_leaf(x, i):
                # identity rows from the host: built on the default device
                # they would be copied chip to chip into the mesh
                i = np.asarray(i)
                tile = np.broadcast_to(i[None], (pad,) + i.shape).astype(x.dtype)
                if isinstance(x, jax.Array):
                    return _concat_rows(x, tile)
                return np.concatenate([np.asarray(x), tile], axis=0)

            tree = jax.tree_util.tree_map(pad_leaf, tree, ident)
        padded.append(tree)
    padded = tuple(padded)

    # cache the jitted program: the closure is new per call, so without this
    # every merge invocation RECOMPILED the whole collective program (tens
    # of seconds of XLA work for a 27-analyzer battery). Keyed by ingest
    # SIGNATURES (class + state shapes), not analyzer identities, so
    # same-shape batteries over different columns share one collective —
    # semigroup ``merge`` is a pure function of class + state shapes.
    from ..runners.engine import _ingest_signature

    shape_sig = tuple(
        (leaf.shape, np.dtype(leaf.dtype).str)
        for leaf in jax.tree_util.tree_leaves(padded)
    )
    cache_key = (
        tuple(_ingest_signature(a) for a in analyzers),
        tuple(mesh.devices.flat),
        k,
        shape_sig,
    )
    program = _COLLECTIVE_MERGE_CACHE.get(cache_key)
    if program is None:
        shard_spec = jax.tree_util.tree_map(
            lambda x: P(ROW_AXIS, *([None] * (np.ndim(x) - 1))), padded
        )
        pow2 = (n_dev & (n_dev - 1)) == 0

        def merge_program(stacked):
            out = []
            for a, tree in zip(analyzers, stacked):
                # 2) local fold of the k resident shards
                acc = jax.tree_util.tree_map(lambda x: x[0], tree)
                for i in range(1, k):
                    acc = a.merge(acc, jax.tree_util.tree_map(lambda x, _i=i: x[_i], tree))
                # 3) cross-device combine
                if n_dev > 1 and pow2:
                    shift = 1
                    while shift < n_dev:
                        perm = [(i, i ^ shift) for i in range(n_dev)]
                        partner = jax.tree_util.tree_map(
                            lambda x: jax.lax.ppermute(x, ROW_AXIS, perm), acc
                        )
                        acc = a.merge(acc, partner)
                        shift <<= 1
                elif n_dev > 1:
                    gathered = jax.tree_util.tree_map(
                        lambda x: jax.lax.all_gather(x, ROW_AXIS), acc
                    )
                    acc = jax.tree_util.tree_map(lambda x: x[0], gathered)
                    for i in range(1, n_dev):
                        acc = a.merge(
                            acc, jax.tree_util.tree_map(lambda x, _i=i: x[_i], gathered)
                        )
                out.append(jax.tree_util.tree_map(lambda x: x[None], acc))
            return tuple(out)

        program = jax.jit(
            _shard_map(
                merge_program,
                mesh=mesh,
                in_specs=(shard_spec,),
                out_specs=shard_spec,
            )
        )
        _COLLECTIVE_MERGE_CACHE[cache_key] = program
    fault_point("collective_merge")
    if jax.process_count() > 1:
        spec = jax.tree_util.tree_map(
            lambda x: P(ROW_AXIS, *([None] * (np.ndim(x) - 1))), padded
        )
        padded = _ensure_global(padded, mesh, spec)
    merged = program(padded)
    # every device holds the identical full merge; take device 0's copy
    # (each PROCESS reads its own addressable replica on a DCN mesh)
    merged = _local_view(merged)
    return _first_rows(merged)


@jax.jit
def _concat_rows(x, tile):
    return jnp.concatenate([x, tile], axis=0)


@jax.jit
def _first_rows(trees):
    """Row 0 of every leaf, in one program: eager indexing would stage its
    index constant on the default device, outside a sub-mesh."""
    return jax.tree_util.tree_map(lambda x: x[0], trees)


# elastic fault tolerance rides on the primitives above; imported LAST so
# the submodules can `from . import sharded_ingest_fold` etc. without a
# cycle (PEP 328 partial-module semantics: the names are already bound)
from .elastic import (  # noqa: E402,F401
    ElasticMeshFold,
    MESH_LADDER_ENV,
    add_shard_loss_listener,
    host_merge_states,
    mesh_batch_quantum,
    mesh_ladder,
    next_rung,
    remove_shard_loss_listener,
    salvage_stacked_states,
    stack_canonical_states,
)
from .health import (  # noqa: E402,F401
    HEARTBEAT_ENV,
    HeartbeatGate,
    probe_devices,
    probe_shards,
    shard_heartbeat_s,
)
