"""Declarative sharding plan: regex partition rules -> PartitionSpec pytrees.

The mesh layer's contract with the rest of the runtime, promoted from an
ad-hoc device list to a first-class object (SNIPPETS [2]/[3] idiom:
``match_partition_rules`` walks a pytree's key paths against ordered regex
rules and yields a `PartitionSpec` pytree; the specs then drive
`shard_map`/`pjit` compilation and `NamedSharding` placement).  Three
invariants live here and are enforced by tpu-lint:

* **Declared axes** (TPU102): every collective in the package names an axis
  from `DECLARED_AXES` — a collective over an undeclared axis either fails
  at trace time on a real mesh or, worse, silently reduces over the wrong
  dimension after a mesh reshape.
* **Local-shape cache keys** (JX505): sharded program builders are keyed by
  `local_signature(...)` — capacity/ring/dtypes only, never the device
  count or a global `[D, ...]` shape — so every device runs the same
  program and adding devices on a rescale never compiles a different key.
* **One mesh axis name per plan**: the data axis is configuration
  (`mesh.axis-rules`), not a per-call argument, so routing, exchange and
  fan-in (`lax.psum`) all agree on the axis they run over.
"""

from __future__ import annotations

import re
import threading
from typing import Any, NamedTuple, Optional, Sequence

import numpy as np

from .mesh import DATA_AXIS, make_mesh, shard_ranges

__all__ = ["AxisRule", "DEFAULT_AXIS_RULES", "DECLARED_AXES",
           "parse_axis_rules", "match_partition_rules",
           "shard_map_unchecked", "local_shape", "ShardingPlan",
           "MeshRuntime", "MESH_RUNTIME"]

# Every mesh axis a collective may legally name. The Tier-A lint rule
# TPU102 (analysis/ast_rules.py) resolves collective axis arguments against
# this tuple; extending the mesh to a second axis (e.g. "model") means
# adding it here FIRST, which is exactly the reviewable event the rule
# wants to force.
DECLARED_AXES = (DATA_AXIS,)


class AxisRule(NamedTuple):
    """One ordered partition rule: leaf paths matching ``pattern`` (full
    match against the "/"-joined key path, e.g. ``accs/price``) get
    `PartitionSpec(*axes)`; ``axes == ()`` replicates."""
    pattern: str
    axes: tuple


# Window-state layout: every persistent leaf leads with the device axis
# ([D, ...] over "data"); everything else (scalars, pane bookkeeping)
# replicates. Callers with exotic state pass their own rules or configure
# `mesh.axis-rules`.
DEFAULT_AXIS_RULES = (
    AxisRule(r"(table|dropped|keys|panes|valid)", (DATA_AXIS,)),
    AxisRule(r"(accs|cols|wins|trees|view)(/.*)?", (DATA_AXIS,)),
    AxisRule(r".*", ()),
)


def parse_axis_rules(text: str, axis_name: str = DATA_AXIS
                     ) -> tuple[AxisRule, ...]:
    """``mesh.axis-rules`` syntax: ``;``-separated ``regex=axis`` entries,
    ``regex=*`` (or ``replicated``) meaning replicate; falls back to
    DEFAULT_AXIS_RULES when empty. A catch-all replicate rule is always
    appended so every leaf resolves."""
    text = (text or "").strip()
    if not text:
        return DEFAULT_AXIS_RULES
    rules = []
    for entry in text.split(";"):
        entry = entry.strip()
        if not entry:
            continue
        if "=" not in entry:
            raise ValueError(
                f"mesh.axis-rules entry {entry!r} is not 'regex=axis'")
        pattern, axis = (s.strip() for s in entry.rsplit("=", 1))
        re.compile(pattern)  # surface bad regexes at configure time
        if axis in ("*", "replicated", ""):
            rules.append(AxisRule(pattern, ()))
        else:
            if axis not in DECLARED_AXES:
                raise ValueError(
                    f"mesh.axis-rules names undeclared axis {axis!r}; "
                    f"declared: {DECLARED_AXES}")
            rules.append(AxisRule(pattern, (axis,)))
    rules.append(AxisRule(r".*", ()))
    return tuple(rules)


def _path_str(path) -> str:
    parts = []
    for k in path:
        name = getattr(k, "name", None)       # GetAttrKey (namedtuples)
        if name is None:
            name = getattr(k, "key", None)    # DictKey / FlattenedIndexKey
        if name is None:
            name = getattr(k, "idx", None)    # SequenceKey
        parts.append(str(name))
    return "/".join(parts)


def match_partition_rules(rules: Sequence[AxisRule], tree: Any):
    """PartitionSpec pytree for ``tree``: each leaf gets the spec of the
    FIRST rule whose pattern fully matches its "/"-joined key path."""
    import jax
    from jax.sharding import PartitionSpec as P

    def spec_for(path: str):
        for rule in rules:
            if re.fullmatch(rule.pattern, path):
                return P(*rule.axes)
        return P()

    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    return jax.tree_util.tree_unflatten(
        treedef, [spec_for(_path_str(p)) for p, _ in flat])


def shard_map_unchecked(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the replication check off: the step emits a
    psum'd replicated scalar next to sharded state, which the static
    checker rejects."""
    import jax
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def local_shape(global_shape: Sequence[int], spec, axis_sizes: dict
                ) -> tuple:
    """Per-device shard shape for a global array under ``spec``: each dim
    named in the spec divides by its mesh-axis size (shard_map semantics:
    exact division is required for sharded dims)."""
    out = list(global_shape)
    for dim, axis in enumerate(tuple(spec)[:len(out)]):
        if axis is None:
            continue
        for ax in ((axis,) if isinstance(axis, str) else axis):
            size = axis_sizes[ax]
            if out[dim] % size:
                raise ValueError(
                    f"dim {dim} of shape {tuple(global_shape)} not "
                    f"divisible by axis {ax!r} (size {size})")
            out[dim] //= size
    return tuple(out)


class ShardingPlan:
    """A mesh + ordered partition rules: the single object the sharded
    window path consults for specs, placement, program mapping, and
    key-group ownership.

    Everything derived from the plan splits into two halves with different
    lifetimes, and keeping them separate is the point of the class:

    * **mesh-dependent** (`sharding`, `device_put`, `shard_map`,
      `ranges`) — changes on rescale;
    * **mesh-independent** (`specs`, `local_signature`) — the program
      cache keys, which must NOT change on rescale so that a worker-set
      change with unchanged local shard shapes recompiles nothing.
    """

    def __init__(self, mesh, rules: Optional[Sequence[AxisRule]] = None,
                 axis_name: str = DATA_AXIS):
        from jax.sharding import NamedSharding, PartitionSpec as P
        self.mesh = mesh
        self.axis_name = axis_name
        self.rules = tuple(rules) if rules else DEFAULT_AXIS_RULES
        self.data_spec = P(axis_name)
        self.state_sharding = NamedSharding(mesh, self.data_spec)

    @property
    def n_devices(self) -> int:
        return int(self.mesh.devices.size)

    @property
    def axis_sizes(self) -> dict:
        return dict(self.mesh.shape)

    # -- mesh-independent ------------------------------------------------
    def specs(self, tree):
        """PartitionSpec pytree for ``tree`` under this plan's rules."""
        return match_partition_rules(self.rules, tree)

    def local_signature(self, tree) -> tuple:
        """Canonical local-shard signature: sorted (path, local shape,
        dtype) per leaf, leading ``"local"`` marker. This is the ONLY
        legal program-cache key component derived from arrays (JX505):
        it is invariant under device count, so a rescale that preserves
        per-device shapes hits every cached program."""
        import jax
        from jax.sharding import PartitionSpec as P
        sizes = self.axis_sizes
        flat, _ = jax.tree_util.tree_flatten_with_path(tree)
        sig = []
        for path, leaf in flat:
            spec = P()
            for rule in self.rules:
                if re.fullmatch(rule.pattern, _path_str(path)):
                    spec = P(*rule.axes)
                    break
            sig.append((_path_str(path),
                        local_shape(np.shape(leaf), spec, sizes),
                        np.dtype(getattr(leaf, "dtype", np.float32)).name))
        return ("local", tuple(sorted(sig)))

    # -- mesh-dependent --------------------------------------------------
    def sharding(self, spec=None):
        from jax.sharding import NamedSharding
        return (self.state_sharding if spec is None
                else NamedSharding(self.mesh, spec))

    def device_put(self, tree):
        """Place a pytree; each leaf lands under its rule's spec."""
        import jax
        specs = self.specs(tree)
        return jax.tree_util.tree_map(
            lambda x, s: jax.device_put(x, self.sharding(s)), tree, specs)

    def shard_map(self, f, in_specs, out_specs):
        return shard_map_unchecked(f, self.mesh, in_specs, out_specs)

    def ranges(self, max_parallelism: int, base=None):
        """Contiguous key-group range per mesh position (see
        mesh.shard_ranges for the remainder rules)."""
        return shard_ranges(max_parallelism, self.n_devices, base)


class MeshRuntime:
    """Process-global mesh configuration (singleton, wired by every deploy
    path next to FAULTS/WATCHDOG/TRACER — enforced by TPU201): the parsed
    `mesh.axis-rules`, and the live-rescale policy knobs the coordinator
    consults. configure() is idempotent and cheap."""

    def __init__(self):
        self._lock = threading.Lock()
        self.axis_rules: tuple = DEFAULT_AXIS_RULES
        self.rescale_enabled: bool = True
        self.rescale_timeout_ms: int = 30_000
        self.configured: bool = False

    def configure(self, config) -> None:
        from ..core.config import MeshOptions
        with self._lock:
            self.axis_rules = parse_axis_rules(
                config.get(MeshOptions.AXIS_RULES))
            self.rescale_enabled = bool(
                config.get(MeshOptions.RESCALE_ENABLED))
            self.rescale_timeout_ms = int(
                float(config.get(MeshOptions.RESCALE_TIMEOUT)) * 1000)
            self.configured = True

    def plan(self, mesh, axis_name: str = DATA_AXIS) -> ShardingPlan:
        return ShardingPlan(mesh, rules=self.axis_rules,
                            axis_name=axis_name)

    def reset(self) -> None:
        with self._lock:
            self.axis_rules = DEFAULT_AXIS_RULES
            self.rescale_enabled = True
            self.rescale_timeout_ms = 30_000
            self.configured = False


MESH_RUNTIME = MeshRuntime()
