"""Live adapter lifecycle: versioned hot-swap banks + serve-while-train.

The engine historically built its stacked adapter bank exactly once: any
``register_adapter`` set ``_serve_tree = None``, and the next step's full
rebuild both RECOMPUTED every live column (``stack_deltas`` is
all-or-nothing about dense vs low-rank, so a dense newcomer would flip
every in-flight low-rank column's representation and its fp rounding) and
re-indexed columns under in-flight requests.  This module makes adapter
registration / update / eviction safe DURING ``run_stream`` — without
draining:

* **Columns are append-only.**  Each mutation materializes at most one new
  bank column per linear via a single-adapter :func:`stack_deltas` +
  :func:`repro.core.registry.extend_bank`; existing columns' arrays are
  only ever concatenated onto (or bit-exactly sliced by compaction), never
  recomputed.  Zero-padding ranks and zero-filled mixed representations
  contribute exact ``+0.0`` terms, so a request admitted before a swap
  decodes the same tokens after it.

* **Epochs pin indices.**  A :class:`BankEpoch` is an immutable
  name -> column view.  Every admitted slot pins the epoch current at its
  admission (plus its resolved bank/draft columns and KV content version);
  mutations advance to a new epoch that only NEW admissions see.  An old
  epoch retires when its last pinned request finishes; compaction then
  slices dead columns out of the device bank (remapping surviving pins)
  to reclaim memory.

* **Swaps are loud and observable.**  Every mutation emits a structured
  :class:`BankSwapEvent` (``engine/bank/swap`` on the engine's
  :mod:`repro.obs` tracker, mirrored on :attr:`AdapterLifecycle.events`)
  plus epoch/column gauges.  A mid-run mutation whose bank extension fails
  (adapter touches a non-linear param, MoE expert, mismatched tree) is
  rolled back — the previous epoch keeps serving, the failure surfaces as
  a warning + ``engine/bank/swap_failed`` event instead of killing the
  in-flight batch.

* **KV never goes stale.**  KV prefix-alias keys are version-qualified
  (``name#version``, monotone per name across re-registration), so an
  updated adapter's requests can never alias a previous version's cached
  pages.

:class:`AdapterFeed` closes the loop with training: it watches a
checkpoint directory (``checkpoint.all_steps`` / ``restore``), and streams
each new fine-tune step into the live bank between engine steps — later
requests serve the newer epoch while in-flight requests finish on theirs
(serve-while-train in one process).  See ``examples/serve_while_train.py``.
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np

from repro.core import registry as peft_registry

PyTree = Any


@dataclasses.dataclass(frozen=True)
class Column:
    """One materialized bank column: a ``(name, version)`` adapter
    snapshot.  Distinct versions of one name are distinct columns while
    both have pinned requests; compaction reclaims the dead one."""
    name: str
    version: int


@dataclasses.dataclass(frozen=True)
class BankSwapEvent:
    """Structured record of one applied bank mutation, emitted as
    ``engine/bank/swap`` on the engine tracker and kept on
    :attr:`AdapterLifecycle.events`.  ``op`` is ``register`` / ``update``
    / ``unregister`` / ``retire`` / ``compact``; ``version`` is the
    per-name content version after the op (the retired epoch id for
    ``retire``, columns reclaimed for ``compact``)."""
    step: int
    op: str
    name: str
    version: int
    epoch: int           # current epoch id after the op
    columns: int         # device-bank column count after the op
    live_epochs: int


class BankEpoch:
    """One immutable name -> column view of the adapter bank.

    ``refs`` counts the in-flight requests pinned to this epoch (pinned at
    admission, released at finish/truncation); a superseded epoch retires
    when the count hits zero, which is what lets compaction prove its
    columns dead.  ``index`` values are remapped in place by compaction —
    the MAPPING is immutable, the physical column numbers are not."""

    __slots__ = ("version", "index", "refs")

    def __init__(self, version: int, index: Dict[str, int]):
        self.version = version
        self.index = index
        self.refs = 0

    def __repr__(self):                                  # pragma: no cover
        return (f"BankEpoch(version={self.version}, refs={self.refs}, "
                f"index={self.index})")


class AdapterLifecycle:
    """Versioned hot-swap state machine for one :class:`ServeEngine`.

    The engine delegates ``_banked_tree()`` here: before the first build,
    mutations apply eagerly to the column plan (cheap — nothing is
    materialized, and with no pins outstanding an update may reuse its
    name's column index in place); once a device tree exists, mutations
    QUEUE and apply at the next :meth:`tree` call — the engine calls that
    at step boundaries, so swaps never land mid-step."""

    def __init__(self, engine, base_name: str,
                 linear_modules: frozenset):
        self.engine = engine
        self.base_name = base_name
        self.linear_modules = linear_modules
        self.columns: List[Column] = [Column(base_name, 0)]
        self.current = BankEpoch(0, {base_name: 0})
        self.live: Dict[int, BankEpoch] = {0: self.current}
        #: every swap/retire/compact, in order (host-side audit trail — the
        #: tracker event stream is the gated observable twin)
        self.events: List[BankSwapEvent] = []
        self.retired_epochs = 0
        self._versions: Dict[str, int] = {base_name: 0}
        # per-name version counters are monotone FOREVER (never reset on
        # unregister): a re-registered name must get a fresh KV alias key,
        # or it could alias retained pages from its previous life
        self._next_version: Dict[str, int] = {base_name: 1}
        self._pending: List[Dict] = []
        self._tree = None
        self._compactable = False

    # -- queries -----------------------------------------------------------
    @property
    def dirty(self) -> bool:
        """Whether :meth:`tree` has work to do (unbuilt, or swaps queued)."""
        return self._tree is None or bool(self._pending)

    def version_of(self, name: str) -> int:
        """Current content version of a live adapter name (the KV
        alias-key qualifier for not-yet-pinned requests)."""
        return self._versions[name]

    def bank_bytes(self) -> int:
        """Device bytes held by bank arrays in the current serve tree (0
        before the first build) — what epoch retirement + compaction
        reclaim."""
        total = 0

        def rec(node):
            nonlocal total
            if isinstance(node, dict):
                if "bank" in node and "w" in node:
                    total += sum(int(np.prod(a.shape)) * a.dtype.itemsize
                                 for a in node["bank"].values())
                    return
                for v in node.values():
                    rec(v)
            elif isinstance(node, list):
                for v in node:
                    rec(v)

        if self._tree is not None:
            rec(self._tree)
        return total

    # -- mutation intake (engine API calls these) --------------------------
    def _bump_version(self, name: str) -> int:
        v = self._next_version.get(name, 0)
        self._next_version[name] = v + 1
        self._versions[name] = v
        return v

    def queue_register(self, name: str, raw, cfg) -> None:
        ver = self._bump_version(name)
        if self._tree is None:
            col = len(self.columns)
            self.columns.append(Column(name, ver))
            index = dict(self.current.index)
            index[name] = col
            self._advance(index, "register", name)
        else:
            self._pending.append({"op": "register", "name": name,
                                  "raw": raw, "cfg": cfg, "version": ver})

    def queue_update(self, name: str, raw, cfg, prev_source,
                     prev_merged) -> None:
        prev_ver = self._versions[name]
        ver = self._bump_version(name)
        if self._tree is None:
            # nothing is pinned before the first build: update IN PLACE,
            # keeping the name's column index (callers that cached a bank
            # index before run() keep a valid one)
            col = self.current.index[name]
            self.columns[col] = Column(name, ver)
            self._advance(dict(self.current.index), "update", name)
        else:
            self._pending.append({"op": "update", "name": name,
                                  "raw": raw, "cfg": cfg, "version": ver,
                                  "prev_source": prev_source,
                                  "prev_merged": prev_merged,
                                  "prev_version": prev_ver})

    def queue_unregister(self, name: str) -> None:
        prev_ver = self._versions.pop(name)
        if self._tree is None:
            col = self.current.index[name]
            del self.columns[col]
            index = {n: (c if c < col else c - 1)
                     for n, c in self.current.index.items() if n != name}
            self._advance(index, "unregister", name)
        else:
            self._pending.append({"op": "unregister", "name": name,
                                  "prev_version": prev_ver})

    # -- epoch machinery ---------------------------------------------------
    def _advance(self, index: Dict[str, int], op: str, name: str) -> None:
        old = self.current
        self.current = BankEpoch(old.version + 1, index)
        self.live[self.current.version] = self.current
        if old.refs == 0:
            self._retire(old)
        self._sync_engine_views()
        self._emit(op, name, self._versions.get(name, -1))

    @staticmethod
    def _payload(ev: BankSwapEvent) -> Dict[str, Any]:
        # event payload keys shadow the tracker record's "step"/"name"
        # (see InMemoryTracker) — rename so events_named() keeps working
        d = dataclasses.asdict(ev)
        d["adapter"] = d.pop("name")
        del d["step"]
        return d

    def _retire(self, ep: BankEpoch) -> None:
        self.live.pop(ep.version, None)
        self.retired_epochs += 1
        self._compactable = True
        if ep is not self.current:
            eng = self.engine
            ev = BankSwapEvent(step=eng._obs_step, op="retire", name="",
                              version=ep.version,
                              epoch=self.current.version,
                              columns=len(self.columns),
                              live_epochs=len(self.live))
            self.events.append(ev)
            if eng._obs:
                eng._tracker.event("engine/bank/epoch_retired",
                                   self._payload(ev), step=eng._obs_step)
                self._gauges()

    def _emit(self, op: str, name: str, version: int) -> None:
        eng = self.engine
        ev = BankSwapEvent(step=eng._obs_step, op=op, name=name,
                          version=version, epoch=self.current.version,
                          columns=len(self.columns),
                          live_epochs=len(self.live))
        self.events.append(ev)
        if eng._obs:
            eng._tracker.event("engine/bank/swap", self._payload(ev),
                               step=eng._obs_step)
            self._gauges()

    def _gauges(self) -> None:
        tr = self.engine._tracker
        s = self.engine._obs_step
        tr.gauge("engine/bank/epoch", self.current.version, step=s)
        tr.gauge("engine/bank/columns", len(self.columns), step=s)

    def _sync_engine_views(self) -> None:
        # keep the engine's historical views coherent: _adapter_index IS
        # the current epoch's mapping, _order the physical column names
        eng = self.engine
        eng._serve_tree = self._tree
        eng._adapter_index = dict(self.current.index)
        eng._order = [c.name for c in self.columns]

    # -- request pinning ---------------------------------------------------
    def pin(self, r, draft_name: Optional[str] = None) -> None:
        """Pin ``r`` to the current epoch at admission: resolve its bank
        column (and its speculative draft's) and stamp its KV content
        version NOW, so later swaps cannot move it.  Re-admission of a
        suspended request keeps its original pin."""
        if getattr(r, "_epoch", None) is not None:
            return
        ep = self.current
        r._bank_col = ep.index[r.adapter]
        r._draft_col = ep.index[draft_name] if draft_name is not None \
            else None
        r._kv_ver = self.columns[r._bank_col].version
        r._epoch = ep
        ep.refs += 1

    def release(self, r) -> None:
        """Drop ``r``'s epoch pin (finish / truncation).  The last release
        of a superseded epoch retires it, making its exclusive columns
        reclaimable by :meth:`compact`."""
        ep = getattr(r, "_epoch", None)
        r._epoch = None
        if ep is None:
            return
        ep.refs -= 1
        if ep.refs == 0 and ep is not self.current:
            self._retire(ep)

    # -- tree building -----------------------------------------------------
    def tree(self):
        """The current serve tree: full build on first use (classic
        all-adapter ``stack_deltas`` walk — bit-identical to the
        historical engine), then append-only extension per queued
        mutation.  A failing mutation is rolled back and re-raised with
        the previous tree intact; later queued mutations stay queued."""
        if self._tree is None:
            self._tree = self._full_build()
            self._sync_engine_views()
            return self._tree
        if self._pending:
            # reclaim dead columns first: the swap already costs this
            # step's one recompile, so compaction rides along free
            self.compact()
            while self._pending:
                mut = self._pending[0]
                try:
                    self._apply(mut)
                except Exception as err:
                    del self._pending[0]
                    self._rollback(mut, err)
                    raise
                del self._pending[0]
        return self._tree

    def _full_build(self):
        """All-columns bank build (the historical ``_banked_tree`` walk,
        relocated): one ``stack_deltas`` per touched linear over every
        column's raw source."""
        eng = self.engine
        base = eng.adapters[self.base_name]
        if [c.name for c in self.columns] == [self.base_name]:
            # the serving base is the base column's own merge: every delta
            # is zero, so no linear gets a bank — skip building them (a
            # fine-tuned base would take the dense path, N·d_in·d_out f32
            # per linear, only to find zeros)
            return base
        entries = [eng._sources[c.name] for c in self.columns]
        pcs = [pc for _, pc in entries]
        names = [c.name for c in self.columns]
        kind_counts = {"left": 0, "delta": 0}

        def rec(node, raws, path):
            if isinstance(node, dict):
                module = path[-1] if path else None
                if set(node) == {"w"} and module in self.linear_modules \
                        and getattr(node["w"], "ndim", 0) >= 2:
                    bank = peft_registry.stack_deltas(
                        node["w"],
                        [(raw, pc, module)
                         for raw, pc in zip(raws, pcs)])
                    if bank is None:
                        return node
                    kind_counts["delta" if "delta" in bank else "left"] += 1
                    if "moe" in path:
                        # expert linears see capacity-dispatched (not
                        # slot-major) activations, so a per-slot gather
                        # would pick deltas by dispatch-buffer row
                        raise ValueError(
                            f"adapter updates MoE expert linear "
                            f"{'/'.join(path)}; per-slot heterogeneous "
                            f"serving does not support expert adapters yet "
                            f"— serve them merged / single-adapter")
                    return {"w": node["w"], "bank": bank}
                return {k: rec(v, [r[k] for r in raws], path + (k,))
                        for k, v in node.items()}
            if isinstance(node, list):
                return [rec(v, [r[i] for r in raws], path + (str(i),))
                        for i, v in enumerate(node)]
            # non-linear leaf: heterogeneous serving shares it — refuse
            # silently-wrong outputs if an adapter changed it
            for name in names[1:]:
                leaf = eng.adapters[name]
                for k in path:
                    leaf = leaf[int(k) if isinstance(leaf, list) else k]
                if not np.array_equal(np.asarray(leaf), np.asarray(node)):
                    raise ValueError(
                        f"adapter {name!r} differs from base at non-linear "
                        f"param {'/'.join(path)}; per-slot serving only "
                        f"covers linear-module updates")
            return node

        tree = rec(base, [raw for raw, _ in entries], ())
        eng._note_bank_kinds(kind_counts)
        return tree

    def _apply(self, mut: Dict) -> None:
        op = mut["op"]
        name = mut["name"]
        if op == "unregister":
            index = {n: c for n, c in self.current.index.items()
                     if n != name}
            self._advance(index, op, name)
            return
        # register / update: materialize exactly one new column
        eng = self.engine
        kind_counts = {"left": 0, "delta": 0}
        new_tree = self._extend_walk(self._tree, mut["raw"], mut["cfg"],
                                     eng.adapters[name], kind_counts, ())
        self._tree = new_tree
        col = len(self.columns)
        self.columns.append(Column(name, mut["version"]))
        index = dict(self.current.index)
        index[name] = col
        self._advance(index, op, name)
        eng._note_bank_kinds(kind_counts)

    def _extend_walk(self, node, raw, cfg, merged, kind_counts, path):
        """Functionally rebuild the serve tree with ONE adapter's column
        appended to every touched linear's bank.  Existing bank arrays are
        never recomputed (:func:`extend_bank`'s exactness contract); a
        failure anywhere leaves ``self._tree`` untouched."""
        n_cols = len(self.columns)
        if isinstance(node, dict):
            module = path[-1] if path else None
            if "bank" in node and "w" in node:
                sub = peft_registry.stack_deltas(node["w"],
                                                 [(raw, cfg, module)])
                if sub is not None:
                    kind_counts["delta" if "delta" in sub else "left"] += 1
                bank = peft_registry.extend_bank(node["w"], node["bank"],
                                                 sub, n_cols, n_new=1)
                return {"w": node["w"], "bank": bank}
            if set(node) == {"w"} and module in self.linear_modules \
                    and getattr(node["w"], "ndim", 0) >= 2:
                sub = peft_registry.stack_deltas(node["w"],
                                                 [(raw, cfg, module)])
                if sub is None:
                    return node
                kind_counts["delta" if "delta" in sub else "left"] += 1
                if "moe" in path:
                    raise ValueError(
                        f"adapter updates MoE expert linear "
                        f"{'/'.join(path)}; per-slot heterogeneous "
                        f"serving does not support expert adapters yet — "
                        f"serve them merged / single-adapter")
                bank = peft_registry.extend_bank(node["w"], None, sub,
                                                 n_cols, n_new=1)
                return {"w": node["w"], "bank": bank}
            return {k: self._extend_walk(v, raw[k], cfg, merged[k],
                                         kind_counts, path + (k,))
                    for k, v in node.items()}
        if isinstance(node, list):
            return [self._extend_walk(v, raw[i], cfg, merged[i],
                                      kind_counts, path + (str(i),))
                    for i, v in enumerate(node)]
        # non-linear leaf: the new adapter's merged value must equal it
        if not np.array_equal(np.asarray(merged), np.asarray(node)):
            raise ValueError(
                f"adapter differs from base at non-linear param "
                f"{'/'.join(path)}; per-slot serving only covers "
                f"linear-module updates")
        return node

    def _rollback(self, mut: Dict, err: Exception) -> None:
        """Undo a failed mutation's engine-side registration so the
        previous epoch keeps serving consistently.  The burned version
        number stays burned (KV alias keys must never repeat)."""
        eng = self.engine
        name = mut["name"]
        if mut["op"] == "register":
            eng.adapters.pop(name, None)
            eng._sources.pop(name, None)
            self._versions.pop(name, None)
        elif mut["op"] == "update":
            raw, cfg = mut["prev_source"]
            eng._sources[name] = (raw, cfg)
            eng.adapters[name] = mut["prev_merged"]
            self._versions[name] = mut["prev_version"]
        ev = BankSwapEvent(step=eng._obs_step, op=f"{mut['op']}_failed",
                          name=name, version=mut.get("version", -1),
                          epoch=self.current.version,
                          columns=len(self.columns),
                          live_epochs=len(self.live))
        self.events.append(ev)
        if eng._obs:
            eng._tracker.count("engine/warnings/swap_failed",
                               step=eng._obs_step)
            eng._tracker.event(
                "engine/bank/swap_failed",
                {**self._payload(ev), "error": str(err)},
                step=eng._obs_step)

    # -- compaction --------------------------------------------------------
    def compact(self) -> int:
        """Slice columns no live epoch references out of the device bank
        (bit-exact gathers — surviving columns keep their values), remap
        every live epoch's index and every pinned request's columns, and
        return the number of columns reclaimed.  Runs automatically ahead
        of the next swap (which already pays the step's recompile);
        :meth:`ServeEngine.compact_banks` exposes it for explicit memory
        reclamation."""
        if self._tree is None or not self._compactable:
            return 0
        self._compactable = False
        referenced = set()
        for ep in self.live.values():
            referenced.update(ep.index.values())
        keep = sorted(referenced)
        dead = len(self.columns) - len(keep)
        if dead == 0:
            return 0
        remap = {old: new for new, old in enumerate(keep)}
        self._tree = self._compact_tree(self._tree, keep)
        self.columns = [self.columns[i] for i in keep]
        for ep in self.live.values():
            ep.index = {n: remap[c] for n, c in ep.index.items()}
        for r in self.engine._pinned_requests():
            if getattr(r, "_bank_col", None) is not None:
                r._bank_col = remap[r._bank_col]
            if getattr(r, "_draft_col", None) is not None:
                r._draft_col = remap[r._draft_col]
        self._sync_engine_views()
        self._emit("compact", "", dead)
        return dead

    def _compact_tree(self, node, keep: Sequence[int]):
        if isinstance(node, dict):
            if "bank" in node and "w" in node:
                bank = peft_registry.take_bank_columns(node["bank"], keep)
                if bank is None:
                    return {"w": node["w"]}
                return {"w": node["w"], "bank": bank}
            return {k: self._compact_tree(v, keep) for k, v in node.items()}
        if isinstance(node, list):
            return [self._compact_tree(v, keep) for v in node]
        return node


# ---------------------------------------------------------------------------
# serve-while-train: checkpoint dir -> live bank
# ---------------------------------------------------------------------------

def adapter_tree(state) -> PyTree:
    """Default :class:`AdapterFeed` extractor: a trainer ``TrainState``
    duck-types to ``adamw.combine(trainable, frozen)`` (the full param
    tree with the fine-tuned PEFT factors in place — see
    :func:`repro.train.trainer.adapter_params`); anything else is assumed
    to already BE the param tree."""
    if hasattr(state, "trainable") and hasattr(state, "frozen"):
        from repro.optim import adamw
        return adamw.combine(state.trainable, state.frozen)
    return state


class AdapterFeed:
    """Stream training checkpoints into a live engine's adapter bank.

    Watches ``ckpt_dir`` and serves the NEWEST unseen checkpoint step as
    adapter ``name``: the first sighting registers it, later ones update
    it (epoch bump — in-flight requests keep their pinned weights,
    requests admitted afterwards serve the new fine-tune state).

    Two discovery paths compose: :meth:`notify` is a thread-safe push
    (hand it to ``checkpoint.save(..., publish=feed.notify)``; async saves
    call it from the writer thread), and :meth:`poll` falls back to a
    directory scan (``checkpoint.all_steps``) every ``poll_every``-th call
    for checkpoints written by another process.  :meth:`attach` wires
    :meth:`poll` into the engine's step hooks so swaps land at engine step
    boundaries — serve-while-train in one process.

    ``template`` is a pytree (or ``jax.eval_shape`` thereof) matching the
    checkpointed object; ``extract`` maps the restored object to the param
    tree to register (default: :func:`adapter_tree`); ``peft_cfg`` is the
    adapter's PEFT config (default: the engine's construction-time one —
    correct when serving checkpoints of the same fine-tune recipe)."""

    def __init__(self, engine, ckpt_dir: str, name: str, template,
                 *, peft_cfg=None, extract: Optional[Callable] = None,
                 poll_every: int = 1, start_after: Optional[int] = None):
        self.engine = engine
        self.ckpt_dir = ckpt_dir
        self.name = name
        self.template = template
        self.peft_cfg = peft_cfg
        self.extract = adapter_tree if extract is None else extract
        self.poll_every = max(int(poll_every), 1)
        #: checkpoint steps streamed into the bank, in order
        self.applied: List[int] = []
        self._last = -1 if start_after is None else int(start_after)
        self._notified: List[int] = []
        self._lock = threading.Lock()
        self._polls = 0

    def notify(self, step: int) -> None:
        """Mark checkpoint ``step`` as freshly published (thread-safe; the
        swap itself happens on the engine thread at the next poll)."""
        with self._lock:
            self._notified.append(int(step))

    def poll(self) -> Optional[int]:
        """Serve the newest unseen checkpoint, if any; returns its step.
        Intermediate steps that appeared since the last poll are skipped
        (the bank serves fine-tune SNAPSHOTS, not the whole history)."""
        from repro.train import checkpoint

        with self._lock:
            notified, self._notified = self._notified, []
        self._polls += 1
        fresh = [s for s in notified if s > self._last]
        if not fresh and (self._polls - 1) % self.poll_every == 0:
            fresh = [s for s in checkpoint.all_steps(self.ckpt_dir)
                     if s > self._last]
        if not fresh:
            return None
        step = max(fresh)
        state = checkpoint.restore(self.template, self.ckpt_dir, step=step)
        params = self.extract(state)
        if self.name in self.engine.adapters:
            self.engine.update_adapter(self.name, params, self.peft_cfg)
        else:
            self.engine.register_adapter(self.name, params, self.peft_cfg)
        self._last = step
        self.applied.append(step)
        return step

    def attach(self) -> "AdapterFeed":
        """Hook :meth:`poll` into the engine's per-step mutation point."""
        self.engine.add_step_hook(self._on_step)
        return self

    def _on_step(self, engine, step: int) -> None:
        self.poll()
