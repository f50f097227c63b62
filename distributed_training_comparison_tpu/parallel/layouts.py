"""Schedule-native state layouts: the resident layout of the trunk stack.

The pipeline schedules consume the stacked transformer trunk in two
different layouts:

- **contiguous** ``(L, feature...)`` — stage ``s`` holds layers
  ``[s*L/P, (s+1)*L/P)``; what GPipe / plain 1F1B / eval / checkpoints /
  the zoo models all speak natively;
- **chunked** ``(v, P, K, feature...)`` — the interleaved schedule's
  view: chunk ``c = i*P + s`` lives on device ``c mod P`` at ``[i, s]``.
  Layer order is i-major, so the reshape IS the chunk assignment — the
  two layouts are plain C-order reshapes of each other, bitwise-neutral
  on host or device.

Before this seam existed the interleaved schedule re-laid the carried
contiguous stack to its chunk view EVERY step (a sharding-constraint
relayout inside the jitted step — an all-to-all of the trunk params per
step on real silicon, invisible on the CPU capture).  Now the schedule's
layout is the *resident* layout: ``TrainState.params["blocks"]`` (and
the optimizer momentum that mirrors it) is carried in whatever layout
the installed schedule declares, and the relayout happens ONCE at
construction/restore instead of per dispatch.

Every reader goes through this one seam instead of inventing its own
view:

- eval / the GPipe fallback canonicalize per eval batch
  (``pipelined_vit_apply(state_layout=...)`` — off the train hot path);
- checkpoints are ALWAYS canonical (contiguous) on disk — the
  interchange format — so any schedule restores any checkpoint; the
  manifest records the *saving* run's resident layout (``state_layout``)
  and ``elastic.validate_reshard`` reports ``state_layout_changed``;
- the parity rail canonicalizes before diffing against the eager
  reference (``run_parity_check(canonicalize_state=...)``);
- the pipeline EF residual (already chunk-laid by construction) derives
  its shapes through ``canonicalized`` so it accepts either resident
  form.

A future schedule declares its own resident layout by registering a
``StateLayout`` here — every reader above picks it up for free.
"""

from __future__ import annotations

import jax.tree_util as jtu
from jax.sharding import PartitionSpec as P

from .mesh import MODEL_AXIS

BLOCKS_KEY = "blocks"


def _path_names(path) -> list:
    """Key names along a key path, across DictKey/GetAttrKey/etc."""
    out = []
    for k in path:
        name = getattr(k, "key", None)
        if name is None:
            name = getattr(k, "name", None)
        out.append(name)
    return out


class StateLayout:
    """The contiguous (canonical) layout: the identity adapter.

    Subclasses override the four leaf/tree hooks; everything else —
    state-wide transforms, the manifest tag, the sharding specs — derives
    from them.  ``to_canonical``/``from_canonical`` must be exact
    inverses and bitwise-neutral (C-order reshapes), so checkpoints,
    desync fingerprints, and the parity rail stay layout-independent.
    """

    kind = "contiguous"
    virtual = 1
    pipe = 1

    def __init__(self, *, pipe_axis: str = MODEL_AXIS, tp_axis: str | None = None):
        self.pipe_axis = pipe_axis
        self.tp_axis = tp_axis

    @property
    def tag(self) -> str:
        """The manifest/event identity string (``state_layout`` field)."""
        return "contiguous"

    def describe(self) -> dict:
        return {"kind": self.kind, "virtual": self.virtual,
                "pipe": self.pipe, "tag": self.tag}

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return f"{type(self).__name__}({self.tag})"

    # -- leaf transforms (identity here) ---------------------------------
    def leaf_from_canonical(self, leaf):
        return leaf

    def leaf_to_canonical(self, leaf):
        return leaf

    def leaf_canonicalized(self, leaf):
        """Idempotent ``leaf_to_canonical``: accepts either form."""
        return leaf

    # -- blocks-subtree transforms ---------------------------------------
    def from_canonical(self, blocks):
        """Canonical ``(L, ...)`` trunk subtree -> resident layout."""
        return jtu.tree_map(self.leaf_from_canonical, blocks)

    def to_canonical(self, blocks):
        """Resident trunk subtree -> canonical ``(L, ...)``."""
        return jtu.tree_map(self.leaf_to_canonical, blocks)

    def canonicalized(self, blocks):
        """Canonical view of ``blocks`` whichever form it arrives in."""
        return jtu.tree_map(self.leaf_canonicalized, blocks)

    # -- sharding --------------------------------------------------------
    def specs(self, blocks):
        """Partition specs for RESIDENT-layout trunk leaves."""
        from .pipeline import pp_trunk_specs

        return pp_trunk_specs(
            blocks, pipe_axis=self.pipe_axis, tp_axis=self.tp_axis
        )


class ChunkedLayout(StateLayout):
    """The interleaved schedule's resident layout: ``(v, P, K, feature...)``.

    ``K = L // (v * P)`` per leaf; the reshape is the chunk assignment
    (chunk ``c = i*P + s`` at ``[i, s]``), so both directions are exact
    C-order reshapes — no data movement on host, one relayout on device.
    """

    kind = "chunked"

    def __init__(
        self,
        virtual: int,
        pipe: int,
        *,
        pipe_axis: str = MODEL_AXIS,
        tp_axis: str | None = None,
    ):
        super().__init__(pipe_axis=pipe_axis, tp_axis=tp_axis)
        if int(virtual) < 2 or int(pipe) < 2:
            raise ValueError(
                f"chunked layout needs virtual >= 2 and pipe >= 2, got "
                f"v={virtual} P={pipe} (v=1 coincides with contiguous)"
            )
        self.virtual = int(virtual)
        self.pipe = int(pipe)

    @property
    def tag(self) -> str:
        return f"chunked:v{self.virtual}:p{self.pipe}"

    def leaf_from_canonical(self, leaf):
        v, p = self.virtual, self.pipe
        depth = int(leaf.shape[0])
        if leaf.ndim < 1 or depth % (v * p):
            raise ValueError(
                f"cannot chunk leaf of shape {tuple(leaf.shape)}: leading "
                f"depth must divide v*P = {v}*{p}"
            )
        return leaf.reshape(v, p, depth // (v * p), *leaf.shape[1:])

    def leaf_to_canonical(self, leaf):
        v, p = self.virtual, self.pipe
        if leaf.ndim < 3 or tuple(leaf.shape[:2]) != (v, p):
            raise ValueError(
                f"leaf of shape {tuple(leaf.shape)} is not in the "
                f"(v={v}, P={p}, K, ...) chunk layout"
            )
        return leaf.reshape(v * p * leaf.shape[2], *leaf.shape[3:])

    def leaf_canonicalized(self, leaf):
        # resident (v, P, K, ...) or already-canonical (L, ...): the two
        # are distinguishable because L = v*P*K >= 2v > v for P >= 2
        if leaf.ndim >= 3 and tuple(leaf.shape[:2]) == (self.virtual, self.pipe):
            return self.leaf_to_canonical(leaf)
        if leaf.shape and int(leaf.shape[0]) % (self.virtual * self.pipe) == 0:
            return leaf
        raise ValueError(
            f"leaf of shape {tuple(leaf.shape)} is neither canonical nor "
            f"in the (v={self.virtual}, P={self.pipe}, K, ...) layout"
        )

    def specs(self, blocks):
        """Specs for the RESIDENT ``(v, P, K, ...)`` trunk: shard axis is
        axis 1 (the stage index); feature dims keep the TP layout."""
        if self.tp_axis is None:
            return jtu.tree_map(lambda _: P(None, self.pipe_axis), blocks)
        from .tp import _vit_trunk_specs

        tp_specs = _vit_trunk_specs(blocks)

        def compose(leaf, spec):
            # resident leaves carry (v, P, K) ahead of the canonical
            # (depth, feature...) dims, so the canonical spec pads to
            # leaf.ndim - 2 entries (its leading depth entry is consumed
            # by the K axis)
            parts = tuple(spec)
            parts = (parts + (None,) * (leaf.ndim - 2 - len(parts)))[
                : leaf.ndim - 2
            ]
            return P(None, self.pipe_axis, None, *parts[1:])

        return jtu.tree_map(compose, blocks, tp_specs)


CONTIGUOUS = StateLayout()


def layout_for(
    schedule: str | None,
    *,
    virtual: int = 1,
    pipe: int = 1,
    pipe_axis: str = MODEL_AXIS,
    tp_axis: str | None = None,
    resident: bool = True,
) -> StateLayout:
    """The resident layout the installed schedule declares.

    Chunked only for the interleaved schedule with real virtual stages
    (``v > 1``) on a real pipe axis; everything else — single device,
    GPipe, plain 1F1B, and ``resident=False`` (the legacy per-step
    relayout, ``--no-pipeline-resident-layout``) — carries the contiguous
    stack.
    """
    if (
        resident
        and schedule == "interleaved"
        and int(virtual) > 1
        and int(pipe) > 1
    ):
        return ChunkedLayout(
            int(virtual), int(pipe), pipe_axis=pipe_axis, tp_axis=tp_axis
        )
    return StateLayout(pipe_axis=pipe_axis, tp_axis=tp_axis)


# per-schedule registry: how a schedule name maps to a layout family.
# ``layout_for`` consults the schedule directly; this table exists so a
# future schedule can declare its resident layout in ONE place and every
# reader (trainer, planner, run_report) picks it up.
SCHEDULE_LAYOUTS = {
    "gpipe": "contiguous",
    "1f1b": "contiguous",
    "interleaved": "chunked",  # when virtual > 1, else contiguous
}


def layout_tag_for(schedule: str | None, *, virtual: int = 1, pipe: int = 1,
                   resident: bool = True) -> str:
    """The ``state_layout`` tag without constructing a layout — what the
    planner stamps on candidates and run_report compares."""
    if (
        resident
        and schedule == "interleaved"
        and int(virtual) > 1
        and int(pipe) > 1
    ):
        return f"chunked:v{int(virtual)}:p{int(pipe)}"
    return "contiguous"


# -- tree-wide transforms -------------------------------------------------
#
# The trunk subtree is keyed "blocks" wherever it appears: under params,
# and mirrored inside the optimizer momentum (optax trace states carry a
# params-shaped tree).  The comms residual also carries a "blocks" key,
# but ITS blocks are schedule-laid by construction (a leading data axis:
# (D, v, P, K, ...)) and are never canonicalized — hence skip_roots.


def _map_blocks_leaves(tree, leaf_fn, *, skip_roots=("comms_residual",)):
    def go(path, leaf):
        names = _path_names(path)
        if names and names[0] in skip_roots:
            return leaf
        if BLOCKS_KEY not in names:
            return leaf
        return leaf_fn(leaf)

    return jtu.tree_map_with_path(go, tree)


def tree_from_canonical(tree, layout: StateLayout, *, skip_roots=("comms_residual",)):
    """Re-lay every trunk (``blocks``-keyed) leaf of ``tree`` from the
    canonical layout into ``layout``'s resident form.  Works on any
    pytree that spells the trunk with a ``blocks`` dict key: params
    trees, optimizer states, serialized checkpoint state dicts."""
    if layout.kind == "contiguous":
        return tree
    return _map_blocks_leaves(
        tree, layout.leaf_from_canonical, skip_roots=skip_roots
    )


def tree_to_canonical(tree, layout: StateLayout, *, skip_roots=("comms_residual",)):
    """Inverse of :func:`tree_from_canonical` (bitwise-exact)."""
    if layout.kind == "contiguous":
        return tree
    return _map_blocks_leaves(
        tree, layout.leaf_to_canonical, skip_roots=skip_roots
    )


def state_from_canonical(state, layout: StateLayout):
    """A ``TrainState`` with params + mirrored optimizer momentum re-laid
    into ``layout``'s resident form.  The one construction/restore-time
    relayout that replaced the per-step one."""
    if layout.kind == "contiguous":
        return state
    return state.replace(
        params=tree_from_canonical(state.params, layout),
        opt_state=tree_from_canonical(state.opt_state, layout),
    )


def state_to_canonical(state, layout: StateLayout):
    """Inverse of :func:`state_from_canonical`: the canonical view every
    layout-independent reader (checkpoints, parity's eager diff,
    fingerprint comparisons across schedules) consumes."""
    if layout.kind == "contiguous":
        return state
    return state.replace(
        params=tree_to_canonical(state.params, layout),
        opt_state=tree_to_canonical(state.opt_state, layout),
    )
