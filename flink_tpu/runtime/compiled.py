"""Fused-chain lowering: the runtime half of the fusion certifier.

The graph-level analysis (graph/fusion.py) proves — statically, before
deployment — that a chained ``source-decode -> ... -> window-step``
prefix is lowerable to one XLA dispatch, and records the proof in the
job's ``FusionCertificate`` (``ChainReport.lowered_prefix``). This
module is what the proof buys at runtime: ``FusedChain`` composes the
device datagen decode and the window operator's ingest step
(``device_window._step_body``) under ONE donated ``jax.jit``, so a
certified micro-batch costs a single device dispatch instead of two
(decode program in the reader + step program in the operator), with
zero host work in between beyond the scalar bookkeeping both paths
already share.

Design points, all load-bearing:

- **Shape-keyed cache, iota as an input.** Programs are cached per
  batch length ``n``. The batch-length dependence is carried by a
  per-``n`` device ``iota = arange(n, int64)`` passed as an INPUT
  (not closed over), so every fused program's abstract signature
  contains an ``((n,), int64)`` leaf and two different batch lengths
  can never collide under the shape-only cache key. ``shape_key``
  reproduces ``analysis/jaxpr_rules._array_signature`` exactly —
  that is the JX603 contract (chain cache keys are shape-only, and
  key equality implies signature equality).

- **Audit before dispatch.** Both the decode prelude (scope
  ``chain.fused_prelude``) and the composed step (scope
  ``chain.fused_step``) register in the program-audit registry BEFORE
  the first dispatch: state buffers are donated, so their shapes are
  only inspectable while the arguments are still alive. The Tier-B
  rules audit these entries: JX601 proves the prelude scatter-free,
  JX602 proves donation survives the composition (input/output
  aliasing present in the lowered chain), JX603 proves the key
  discipline above.

- **Exact decode semantics.** The fused decode reproduces the
  reader's per-batch program bit for bit: same global index math
  ``(start + iota) * stride + subtask``, same per-field ``astype``,
  same monotonicity outputs (in-batch violation OR'd with the
  cross-batch tail comparison, plus the batch's last timestamp).
  The (viol, last) outputs are handed back to the reader through
  ``LazyDeviceBatch.deliver`` — fused and unfused runs are
  byte-identical, including the deferred contract check.

- **No note_build.** Like the reader's per-``n`` decode cache, fused
  chain compiles are not counted in ``DEVICE_STATS.compiles`` — the
  recompile budget tracks the instrumented program caches, and the
  bench acceptance gate (recompiles == 0 in the timed stage) holds
  for fused runs exactly as for unfused ones. Dispatches are counted
  (``chain_fused_dispatches_total``): exactly one per micro-batch is
  the observable the acceptance test asserts.
"""

from __future__ import annotations

import time
from functools import partial
from typing import Any

import numpy as np

from ..metrics.device import DEVICE_STATS, _record_program_audit
from ..metrics.profiler import DEVICE_LEDGER

__all__ = ["CHAIN_PRELUDE_SCOPE", "CHAIN_STEP_SCOPE", "shape_key",
           "FusedChain"]

# audit scopes — jaxpr_rules keys its chain rules off these exact names
CHAIN_PRELUDE_SCOPE = "chain.fused_prelude"
CHAIN_STEP_SCOPE = "chain.fused_step"


def shape_key(args: tuple, kwargs: dict | None = None) -> str:
    """Shape-only cache key over a call's arguments — the runtime twin
    of ``analysis/jaxpr_rules._array_signature`` (must stay
    representation-identical: JX603 checks ``build_key`` equality
    against that function's output over the audited abstract args)."""
    import jax

    leaves = jax.tree_util.tree_leaves((args, kwargs or {}))
    sig = []
    for leaf in leaves:
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is not None and dtype is not None:
            sig.append((tuple(shape), str(dtype)))
    return repr(sig)


# Process-global chain program cache, mirroring the step program's
# instrumented_program_cache: a fused job pays the chain compile once
# per (source gen, schema, placement, fold signature, geometry, batch
# length) for the life of the process, not once per deployed operator —
# without this every env.execute() recompiles the chain and the fused
# path loses its dispatch savings to fixed compile cost. Keyed on the
# gen FUNCTION OBJECT (not its code) so two closures with different
# captured constants can never share a program.
# lint: guarded-by single-writer — mutated only via FusedChain.run on the task mailbox thread
_PROGRAM_CACHE: dict = {}
_MAX_PROGS = 64


class FusedChain:
    """Composed decode+step programs for one certified chain, one per
    batch length (the reader's power-of-two bucketing bounds the
    population exactly as it bounds its own ``_progs``). Programs live
    in the module-global ``_PROGRAM_CACHE`` keyed by everything the
    build closes over, so redeploys of the same pipeline reuse them."""

    def __init__(self, source, subtask: int, parallelism: int,
                 key_column: str, fold_sig: tuple, ring: int, pane: int,
                 offset: int, dirty_block: int, count_kind: str = "count"):
        self._src = source
        self._subtask = int(subtask)
        self._parallelism = int(parallelism)
        self._key_column = key_column
        self._sig = tuple(fold_sig)
        self._ring = int(ring)
        self._pane = int(pane)
        self._offset = int(offset)
        self._dirty_block = int(dirty_block)
        self._count_kind = count_kind
        src = self._src
        self._cache_key = (
            src._gen, tuple((f.name, str(f.dtype)) for f in src.schema.fields),
            src._ts_col, self._subtask, self._parallelism, key_column,
            self._sig, self._ring, self._pane, self._offset,
            self._dirty_block, count_kind)

    # -- program construction ---------------------------------------------
    def _build(self, n: int) -> dict[str, Any]:
        import jax
        import jax.numpy as jnp

        from ..ops.hash_table import ensure_x64
        from .operators.device_window import _step_body

        ensure_x64()
        s = self._src
        stride, off = self._parallelism, self._subtask
        fields = s.schema.fields
        ts_col = s._ts_col
        sig = self._sig
        key_col = self._key_column
        step = _step_body(sig, self._ring, self._pane, self._offset,
                          self._dirty_block, 0, self._count_kind)

        def decode(iota, start, prev_last):
            # identical integer math to _DeviceDataGenReader._program —
            # fused and unfused runs must be byte-identical
            idx = (start + iota) * stride + off
            cols = s._gen(idx)
            out = {f.name: jnp.asarray(cols[f.name]).astype(f.dtype)
                   for f in fields}
            ts = out[ts_col]
            viol = (jnp.any(ts[1:] < ts[:-1])
                    | (ts[0].astype(jnp.int64) < prev_last))
            return out, ts.astype(jnp.int64), viol, ts[-1].astype(jnp.int64)

        # the decode alone, registered under the prelude scope so JX601
        # can prove the fused prefix scatter-free in isolation
        prelude = jax.jit(decode)

        @partial(jax.jit, donate_argnums=(1, 2, 3, 4, 5))
        def chain(iota, table, arrays, dropped, late, dirty, start,
                  prev_last, first_open):
            out, ts, viol, last = decode(iota, start, prev_last)
            cols = {f: out[f] for _k, _n, f in sig}
            table, arrays, dropped, late, dirty, _stage, _touch, token = \
                step(table, arrays, dropped, late, dirty, None, None,
                     out[key_col], ts, cols, None, jnp.int64(0),
                     first_open, n)
            return table, arrays, dropped, late, dirty, viol, last, token

        return {"chain": chain, "prelude": prelude,
                "iota": jnp.arange(n, dtype=jnp.int64), "registered": False}

    # -- dispatch ----------------------------------------------------------
    def run(self, n: int, start, prev_last, table, arrays, dropped, late,
            dirty, first_open):
        """One fused dispatch: decode batch [start, start+n) and fold it
        into the donated window state. Returns the step outputs plus the
        decode's (viol, last) for ``LazyDeviceBatch.deliver``."""
        key = self._cache_key + (n,)
        prog = _PROGRAM_CACHE.get(key)
        if prog is None:
            if len(_PROGRAM_CACHE) >= _MAX_PROGS:
                _PROGRAM_CACHE.pop(next(iter(_PROGRAM_CACHE)))
            prog = self._build(n)
            _PROGRAM_CACHE[key] = prog
        args = (prog["iota"], table, arrays, dropped, late, dirty,
                np.int64(start), prev_last, np.int64(first_open))
        if not prog["registered"]:
            # before the dispatch: donation consumes the state buffers,
            # after which their shapes are gone
            prog["registered"] = True
            pargs = (prog["iota"], np.int64(start), prev_last)
            _record_program_audit(CHAIN_PRELUDE_SCOPE, prog["prelude"],
                                  pargs, {}, shape_key(pargs))
            _record_program_audit(CHAIN_STEP_SCOPE, prog["chain"],
                                  args, {}, shape_key(args))
            prog["sig"] = shape_key(args)
            # ledger marker for the prelude program: zero-duration by
            # design — its trace/compile cost is paid inside the first
            # fused-step dispatch, which is charged below
            DEVICE_LEDGER.record("chain.fused_prelude", 0.0,
                                 shape_sig=shape_key(pargs),
                                 kind="compile")
        timed = DEVICE_LEDGER.enabled
        t0 = time.perf_counter() if timed else 0.0
        out = prog["chain"](*args)
        if timed:
            # the first dispatch traces/lowers/compiles synchronously:
            # charge it as compile time, not a steady-state sample
            DEVICE_LEDGER.record(
                "chain.fused_step", (time.perf_counter() - t0) * 1e3,
                shape_sig=prog.get("sig", ""),
                kind="dispatch" if prog.get("compiled") else "compile")
        prog["compiled"] = True
        DEVICE_STATS.note_chain_dispatch()
        return out
