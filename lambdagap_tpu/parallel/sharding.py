"""Unified partition-rule registry: the one source of truth for how every
array in the training state is laid out over the device mesh.

Before this module each parallel learner declared its own ad-hoc
``PartitionSpec`` literals (data_parallel / fused_parallel / voting_parallel
/ feature_parallel all hardcoded ``P(DATA_AXIS, ...)`` tuples), so the same
logical array — the packed binned matrix, a gradient buffer, a histogram —
was sharded by four independent spellings, and a 2-D (data x feature) mesh
could not even be expressed. Here every logical array NAME resolves through
one ordered rule table (the ``match_partition_rules`` regex pattern of
SNIPPETS.md [3], over the mesh-helper shape of [1]) against a mesh that
always declares BOTH axes::

    Mesh(devices.reshape(dd, ff), ("data", "feature"))

A data-parallel placement is ``(D, 1)``, a feature-parallel placement is
``(1, D)``, and a future 2-D run is ``(dd, ff)`` — the RULES never change,
only the mesh geometry does, because a ``PartitionSpec`` axis over a
size-1 mesh dimension is a no-op. That is what makes the registry the 2-D
unlock: ``x_rows -> P("data", "feature")`` already says "rows over the
data axis AND columns over the feature axis"; today's learners simply run
it at geometries where one of the two is trivial.

graftlint R6 reads ``MESH_AXES`` below as the collective-axis universe
(analysis/rules/r6_collective_axis.py): a ``psum``/``all_gather`` naming an
axis this registry does not declare is flagged without running any code.

The feature->rank ownership tables of the reference's distributed learners
(reference: src/treelearner/data_parallel_tree_learner.cpp:71-121
PrepareBufferPos) have no analog here: ownership IS the partition spec.
"""
from __future__ import annotations

import re
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax import shard_map  # noqa: F401  (re-exported to the learners)
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# the axis universe. Rows of the training matrix shard over "data"
# (histograms psum over it); columns shard over "feature" (histogram
# blocks all_gather / winning columns psum over it).
DATA_AXIS = "data"
FEATURE_AXIS = "feature"
MESH_AXES = (DATA_AXIS, FEATURE_AXIS)

# ---------------------------------------------------------------------------
# the rule table
# ---------------------------------------------------------------------------
# name-regex -> PartitionSpec template, first match wins (SNIPPETS.md [3]).
# Templates name MESH_AXES members or None per array dimension; a template
# shorter than the array rank is padded with None (trailing dims
# replicated). Every array the parallel learners move through shard_map
# has a named rule here — an unmatched name raises, never guesses.
RULES: Tuple[Tuple[str, Tuple[Optional[str], ...]], ...] = (
    # packed binned matrix, row-major [rows, features]
    (r"^(x|hx)_rows$|^x_sharded$", (DATA_AXIS, FEATURE_AXIS)),
    # column-major copy [features, rows] (partition-pass column reads)
    (r"^(x|hx)_cols$", (FEATURE_AXIS, DATA_AXIS)),
    # fully replicated matrix (the host-loop feature learner keeps all
    # rows everywhere and block-slices columns by axis_index itself)
    (r"^x_replicated$", ()),
    # sorted-leaf payload, word-major [lanes, rows + W]: a shard holds its
    # rows' every lane
    (r"^srows$|^sorted_(rows|payload)$", (None, DATA_AXIS)),
    # per-row training state: gh buffers, quantized gh levels, sample /
    # pad masks, permutations, scores, row->leaf maps
    (r"^(grad|hess|gq|hq)$|^(row_|real_)?mask$|^perm$|^score$|^row_leaf$",
     (DATA_AXIS,)),
    # per-shard scalar bookkeeping distributed one-per-device along the
    # data axis (leaf begin/count blocks of the host-loop learners)
    (r"^(begin|count)$|^shard_scalar$", (DATA_AXIS,)),
    # device-stacked local histograms [D*F, B, 3] (voting keeps histograms
    # shard-local and psums only voted columns)
    (r"^hist_(local|stack)$", (DATA_AXIS,)),
    # 2-D program arrays (the fused data x feature learner + its stream
    # mirror): histogram COLUMN blocks shard over "feature" while their
    # row partials psum over "data" —
    #   hist_cols  [C, B, 3]            one leaf's histogram, psum-ed over
    #                                   data, column-sharded
    #   hist_state [L+1, C, B, 3]       the carried per-leaf histogram state
    #   hist_grid  [dd, C, B, 3]        per-(data,feature)-device partial
    #                                   accumulator of the stream pump
    #   win_bins   [dd, W, C]           one uploaded row window per data
    #                                   block, columns sharded
    #   win_cvals  [dd, PV]             per-block per-lane values (split
    #                                   column / compaction positions)
    #   leaf_local [dd, L+1, k]         per-data-shard leaf bookkeeping
    #                                   (begin/count are row-partition
    #                                   quantities — local per data block,
    #                                   replicated over feature)
    (r"^hist_cols$", (FEATURE_AXIS,)),
    (r"^hist_state$", (None, FEATURE_AXIS)),
    (r"^hist_grid$", (DATA_AXIS, FEATURE_AXIS)),
    (r"^win_bins$", (DATA_AXIS, None, FEATURE_AXIS)),
    (r"^win_(cvals|pos|lanes)$", (DATA_AXIS,)),
    (r"^leaf_local$", (DATA_AXIS,)),
    # predict_stream batch-scoring arrays (infer/stream.py): scoring is
    # collective-free and strictly per-row, so window rows shard over the
    # WHOLE flattened grid — both mesh axes on the row dim — and every
    # dd x ff factorization (1x8, 2x4, 8x1) runs the one program on its
    # local rows:
    #   pred_win    [W, F]   one padded scoring window, rows sharded,
    #                        features replicated
    #   pred_scores [K, W]   its score tile riding the D2H ring back,
    #                        rows sharded the same way
    (r"^pred_win$", ((DATA_AXIS, FEATURE_AXIS), None)),
    (r"^pred_scores$", (None, (DATA_AXIS, FEATURE_AXIS))),
    # replicated state: psum-ed histograms, split results, node/leaf
    # tables, per-feature metadata, feature sampling masks, rng keys,
    # scalars. Derived from collectives on every shard -> identical
    # everywhere by construction.
    (r"^hist(ogram)?(_root)?$|^fmask$|^(feature|bin)_meta$|^node(_\w+)?$"
     r"|^leaf(_\w+)?$|^tree(_record)?$|^(e|q|b)?key$|^scalar$"
     r"|^rep(licated)?$", ()),
)


def spec(name: str, ndim: Optional[int] = None) -> P:
    """The :class:`PartitionSpec` for the logical array ``name``.

    ``ndim`` pads the matched template with trailing ``None`` dims (a
    per-row rule applied to an ``[N, k]`` array); templates are never
    truncated. Unknown names raise — the registry must stay exhaustive
    (same contract as SNIPPETS.md [3] ``match_partition_rules``).
    """
    for pattern, template in RULES:
        if re.search(pattern, name):
            if ndim is not None:
                if ndim < len(template):
                    raise ValueError(
                        f"array {name!r} has rank {ndim} but its partition "
                        f"rule spans {len(template)} dims")
                template = template + (None,) * (ndim - len(template))
            return P(*template)
    raise ValueError(
        f"no partition rule for array {name!r}; add one to "
        "lambdagap_tpu/parallel/sharding.py RULES")


def specs(*names: str) -> Tuple[P, ...]:
    """``spec`` over several names — the ``in_specs=specs(...)`` helper."""
    return tuple(spec(n) for n in names)


def sharding(mesh: Mesh, name: str, ndim: Optional[int] = None
             ) -> NamedSharding:
    return NamedSharding(mesh, spec(name, ndim))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


# ---------------------------------------------------------------------------
# mesh construction
# ---------------------------------------------------------------------------
def parse_mesh_shape(mesh_shape: str) -> Optional[Tuple[int, int]]:
    """``mesh_shape`` knob -> (data, feature) extents. ``""`` -> None
    (learner picks its natural 1-D placement); ``"8"`` -> (8, 1);
    ``"4x2"`` -> (4, 2). ``0`` in either slot means "all remaining
    devices on this axis"."""
    s = str(mesh_shape).strip().lower()
    if not s:
        return None
    parts = s.replace("*", "x").split("x")
    try:
        dims = [int(p) for p in parts]
    except ValueError:
        raise ValueError(f"mesh_shape must look like '8' or '4x2', "
                         f"got {mesh_shape!r}")
    if len(dims) == 1:
        dims.append(1)
    if len(dims) != 2 or any(d < 0 for d in dims):
        raise ValueError(f"mesh_shape must be 1-D or 2-D non-negative, "
                         f"got {mesh_shape!r}")
    return dims[0], dims[1]


def resolve_mesh_shape(mesh_shape: str, num_devices: int
                       ) -> Optional[Tuple[int, int]]:
    """Resolve the ``mesh_shape`` knob against an actual device count:
    wildcard extents (``"0x4"`` / ``"2x0"`` — "all remaining devices on
    this axis") are filled in, divisibility and capacity are checked, and
    every rejection names ``mesh_shape`` (the ``num_grad_quant_bins``
    error-message precedent). ``""`` -> None (the learner picks its
    natural 1-D placement)."""
    shape = parse_mesh_shape(mesh_shape)
    if shape is None:
        return None
    dd, ff = shape
    if dd == 0 and ff == 0:
        raise ValueError("mesh_shape cannot be 0x0 (at most one wildcard "
                         "extent)")
    if dd == 0:
        if num_devices % max(ff, 1):
            raise ValueError(
                f"mesh_shape {mesh_shape!r}: the wildcard data extent "
                f"needs the device count ({num_devices}) divisible by the "
                f"feature extent ({ff})")
        dd = num_devices // ff
        if dd == 0:
            raise ValueError(
                f"mesh_shape {mesh_shape!r} needs at least {ff} devices, "
                f"have {num_devices}")
    if ff == 0:
        if num_devices % max(dd, 1):
            raise ValueError(
                f"mesh_shape {mesh_shape!r}: the wildcard feature extent "
                f"needs the device count ({num_devices}) divisible by the "
                f"data extent ({dd})")
        ff = num_devices // dd
        if ff == 0:
            raise ValueError(
                f"mesh_shape {mesh_shape!r} needs at least {dd} devices, "
                f"have {num_devices}")
    if dd * ff > num_devices:
        raise ValueError(
            f"mesh_shape {mesh_shape!r} ({dd}x{ff}) needs {dd * ff} "
            f"devices, have {num_devices}")
    return dd, ff


def make_mesh(num_devices: int = 0, devices: Optional[Sequence] = None,
              mesh_shape: str = "", shard_axis: str = DATA_AXIS) -> Mesh:
    """The registry mesh: ALWAYS 2-D named ``("data", "feature")``.

    ``mesh_shape=""`` places ``num_devices`` (0 = all visible) on
    ``shard_axis`` — the learner's natural 1-D geometry: data/voting
    learners shard rows (``(D, 1)``), feature learners shard columns
    (``(1, D)``); asking for more devices than are visible raises, naming
    ``tpu_num_devices``. An explicit ``mesh_shape`` overrides both knobs —
    including genuine 2-D ``dd x ff`` grids, executed by the fused 2-D
    learner (rows shard over ``data``, histogram columns over
    ``feature``; parallel/fused_parallel.py Fused2DTreeLearner).
    """
    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    shape = resolve_mesh_shape(mesh_shape, len(devices))
    if shape is None:
        if num_devices and num_devices > 0:
            if num_devices > len(devices):
                raise ValueError(
                    f"tpu_num_devices={num_devices} needs {num_devices} "
                    f"devices, have {len(devices)}")
            devices = devices[:num_devices]
        d = len(devices)
        shape = (d, 1) if shard_axis == DATA_AXIS else (1, d)
    else:
        devices = devices[:shape[0] * shape[1]]
    arr = np.asarray(devices).reshape(shape)
    return Mesh(arr, MESH_AXES)


def mesh_geometry(mesh: Mesh) -> dict:
    """JSON-able mesh description for snapshot sidecars / bench records /
    telemetry run headers (guard elastic resume reads it back)."""
    shape = dict(mesh.shape)
    return {
        "axes": list(mesh.axis_names),
        "shape": [int(shape.get(a, 1)) for a in mesh.axis_names],
        "n_devices": int(mesh.devices.size),
        "platform": str(mesh.devices.reshape(-1)[0].platform),
    }
