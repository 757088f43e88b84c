"""Content-addressed semantic caching for the serve loop (ISSUE 13).

At millions of users traffic is Zipfian — identical and near-identical
requests dominate — so the cheapest request is the one the engine never
computes. A :class:`SemCache` sits *above* the two-pool engine and serves
three layers, addressed by the request's ``content_key``
(``serve.request.content_key``: every output-determining field, nothing
else):

- **L1 — text-encoder outputs.** Cond/uncond embeddings are pure functions
  of ``(model, prompt)``; the runners memoize them here (bounded LRU with
  bytes accounting), so a popular prompt pays the text encoder once per
  process instead of once per lane.
- **L2 — phase-1 carry prefix.** A gated request's hand-off carry is a
  pure function of its content key, and the engine already knows how to
  *resume* a request from a spilled carry (the journal's crash-replay
  path). Every hand-off spills a copy here (content-addressed ``.npz``
  via ``handoff.spill_carry``); a later request with the same content key
  loads it (template-validated via ``handoff.load_carry`` — a corrupt or
  mismatched spill is a **silent miss + recompute, never a fault**) and
  enters the engine directly in phase 2: a prefix hit IS a hand-off
  resume.
- **L3 — exact results.** The leader's terminal images, returned bitwise.
  Entries spill to content-addressed ``.npz`` files so they survive a
  crash: the engine journals a ``cache`` record per insert and replay
  reseeds the index (``SemCache.seed``), which is what lets a restart
  serve a killed leader's followers without recomputing (the
  ``kill_after_cache_insert`` chaos drill). The ordering that makes it
  sound — the ``cache`` record lands *before* the leader's terminal, so
  no follower can dedupe against a terminal whose result never became
  durable — is a declared invariant (``cache-before-terminal`` in
  ``p2p_tpu.analysis.walcheck``, ISSUE 20), model-checked at every
  crash point and guarded by the ``terminal-before-cache`` seeded bug.
  In-memory residency is bounded by ``l3_bytes`` (LRU; eviction deletes
  the spill file too).

Single-flight collapsing (identical in-flight requests ride one leader)
lives in the engine, not here — the cache is pure storage; the engine owns
the clock and the record stream.

Eviction joins the degradation ladder: under sustained pressure the engine
calls :meth:`shed_l2` *before* it sheds requests — spill disk is the
cheapest thing the server owns.

Everything is strictly opt-in: ``semcache=None`` (the default everywhere)
leaves the record stream, journal bytes, compiled programs and metric
families byte-identical to the pre-cache engine — the disabled-mode parity
discipline every serve subsystem pins.
"""

from __future__ import annotations

import functools
import hashlib
import os
import tempfile
from collections import OrderedDict
from typing import Any, Dict, Optional, Tuple

from ..obs import metrics as obs_metrics

LAYERS = ("l1", "l2", "l3")


@functools.lru_cache(maxsize=65536)
def digest(key: Tuple) -> str:
    """Stable content address for any hashable key tuple. ``repr`` is the
    serialization: content keys are flat tuples of python scalars/strings,
    so equal keys repr identically across processes. Memoized: the engine
    digests the same key at admission, leader registration and hand-off
    spill — and popular traffic repeats keys by construction."""
    return hashlib.sha256(repr(key).encode()).hexdigest()[:32]


class SemCache:
    """Three-layer content-addressed cache. One instance covers one serve
    process; the engine consults it at admission (L3/L2) and the runners
    at encode time (L1).

    ``spill_dir`` holds the L2/L3 sidecar files (content-addressed names,
    written tmp+rename so a crash never leaves a torn file that parses);
    default: a fresh tempdir. ``layers`` opts layers in individually —
    a layer not listed never stores, never hits, never counts."""

    def __init__(self, spill_dir: Optional[str] = None,
                 l1_bytes: int = 32 << 20, l2_entries: int = 256,
                 l3_bytes: int = 256 << 20,
                 layers: Tuple[str, ...] = LAYERS):
        for layer in layers:
            if layer not in LAYERS:
                raise ValueError(f"unknown cache layer {layer!r}; "
                                 f"valid: {', '.join(LAYERS)}")
        if l1_bytes < 1 or l2_entries < 1 or l3_bytes < 1:
            raise ValueError("cache budgets must be >= 1")
        self.layers = tuple(layers)
        self.l1_bytes = l1_bytes
        self.l2_entries = l2_entries
        self.l3_bytes = l3_bytes
        self.spill_dir = spill_dir or tempfile.mkdtemp(prefix="p2p-semcache-")
        os.makedirs(self.spill_dir, exist_ok=True)
        # Open-time hygiene (the journal's carry-dir idiom): a crash
        # mid-spill leaves only a .tmp (the rename is atomic), and a
        # previous incarnation's L2 prefix spills are unreachable by
        # construction — the L2 index is memory-only, so a reused
        # --cache-dir would otherwise leak p1-* files forever. L3 r-*
        # spills are NOT swept here: the journal may reference them
        # (``seed`` is the authority — it sweeps what replay does not).
        for name in os.listdir(self.spill_dir):
            if name.endswith(".tmp") or (name.startswith("p1-")
                                         and name.endswith(".npz")):
                try:
                    os.remove(os.path.join(self.spill_dir, name))
                except OSError:
                    pass
        self._l1: "OrderedDict[Tuple, Tuple[Any, int]]" = OrderedDict()
        self._l1_used = 0
        self._l2: "OrderedDict[str, Dict]" = OrderedDict()
        self._l3: "OrderedDict[str, Dict]" = OrderedDict()
        self._l3_used = 0
        self.stats = {layer: {"hits": 0, "misses": 0, "inserts": 0,
                              "evictions": 0, "corrupt": 0}
                      for layer in LAYERS}
        reg = obs_metrics.registry()
        self._m_events = reg.counter(
            "serve_semcache_events_total",
            "semantic-cache lookups/inserts/evictions by layer and event",
            labels=("layer", "event"))
        self._m_bytes = reg.gauge(
            "serve_semcache_bytes",
            "bytes resident per semantic-cache layer (L2: spill disk)",
            labels=("layer",))

    def enabled(self, layer: str) -> bool:
        return layer in self.layers

    digest = staticmethod(digest)

    def _note(self, layer: str, event: str, n: int = 1) -> None:
        self.stats[layer][event] += n
        self._m_events.labels(layer=layer, event=event).inc(n)

    def note_miss(self, layer: str) -> None:
        """Count one lookup miss decided OUTSIDE the store: the engine
        tests presence first (``l3_has``/``l2_has``) so admission can
        reject a request before any cache counter moves, then records
        the miss only once the request is actually admitted — keeping
        hits+misses == lookups of admitted traffic."""
        if self.enabled(layer):
            self._note(layer, "misses")

    # -- L1: text-encoder outputs -----------------------------------------

    def l1_get_or_build(self, key: Tuple, build):
        """Memoized encode: returns the cached value for ``key`` or builds,
        stores (bytes-bounded LRU) and returns it. Values are the device
        arrays the encoder produced — reuse is bitwise by construction."""
        if not self.enabled("l1"):
            return build()
        if key in self._l1:
            self._l1.move_to_end(key)
            self._note("l1", "hits")
            return self._l1[key][0]
        self._note("l1", "misses")
        import jax

        value = build()
        nbytes = sum(int(getattr(leaf, "size", 0)) * int(
            getattr(getattr(leaf, "dtype", None), "itemsize", 0) or 0)
            for leaf in jax.tree_util.tree_leaves(value))
        self._l1[key] = (value, nbytes)
        self._l1_used += nbytes
        self._note("l1", "inserts")
        while self._l1_used > self.l1_bytes and len(self._l1) > 1:
            _, (_, freed) = self._l1.popitem(last=False)
            self._l1_used -= freed
            self._note("l1", "evictions")
        self._m_bytes.labels(layer="l1").set(self._l1_used)
        return value

    # -- L2: phase-1 carry prefix -----------------------------------------

    def _l2_path(self, key_digest: str) -> str:
        return os.path.join(self.spill_dir, f"p1-{key_digest}.npz")

    def l2_has(self, key_digest: str) -> bool:
        return self.enabled("l2") and key_digest in self._l2

    def l2_put(self, key_digest: str, carry: Any) -> None:
        """Spill one per-lane hand-off unit under its content address
        (``handoff.spill_carry``: tmp+rename+fsync). Entry-bounded LRU;
        eviction deletes the spill file."""
        if not self.enabled("l2"):
            return
        if key_digest in self._l2:
            self._l2.move_to_end(key_digest)
            return
        from .handoff import spill_carry

        path = self._l2_path(key_digest)
        spec = spill_carry(carry, path)
        self._l2[key_digest] = {"path": path, "spec": spec,
                                "bytes": os.path.getsize(path)}
        self._note("l2", "inserts")
        while len(self._l2) > self.l2_entries:
            self._evict_l2(next(iter(self._l2)), "evictions")
        self._update_l2_bytes()

    def l2_get(self, key_digest: str, template: Any) -> Optional[Any]:
        """Load a prefix carry, validated leaf-by-leaf against the treedef
        the *request* implies (``handoff.load_carry``). Any mismatch or
        unreadable file — a template refusal, a corrupt entry, operator
        damage — is a silent miss: the entry is dropped and the caller
        recomputes phase 1. A wrong-shaped carry must never reach a
        compiled program, and a bad cache entry must never fail a
        request."""
        if not self.enabled("l2"):
            return None
        entry = self._l2.get(key_digest)
        if entry is None:
            self._note("l2", "misses")
            return None
        from .handoff import load_carry

        try:
            carry = load_carry(entry["path"], template)
        except ValueError:
            self._note("l2", "corrupt")
            self._note("l2", "misses")
            self._evict_l2(key_digest, None)
            self._update_l2_bytes()
            return None
        self._l2.move_to_end(key_digest)
        self._note("l2", "hits")
        return carry

    def _evict_l2(self, key_digest: str, count_as: Optional[str]) -> None:
        entry = self._l2.pop(key_digest, None)
        if entry is None:
            return
        try:
            os.remove(entry["path"])
        except OSError:
            pass
        if count_as:
            self._note("l2", count_as)

    def _update_l2_bytes(self) -> None:
        self._m_bytes.labels(layer="l2").set(
            sum(e["bytes"] for e in self._l2.values()))

    def shed_l2(self) -> int:
        """Drop every L2 entry and its spill disk — the degradation
        ladder's cheapest rung, taken *before* any request is shed.
        Returns how many entries went."""
        n = len(self._l2)
        for key_digest in list(self._l2):
            self._evict_l2(key_digest, "evictions")
        self._update_l2_bytes()
        return n

    # -- L3: exact results -------------------------------------------------

    def _l3_path(self, key_digest: str) -> str:
        return os.path.join(self.spill_dir, f"r-{key_digest}.npz")

    def l3_has(self, key_digest: str) -> bool:
        """Presence only — no counters move (the engine's pre-admission
        test; a not-yet-lazy-loaded seeded entry counts as present)."""
        return self.enabled("l3") and key_digest in self._l3

    def l3_put(self, key_digest: str, images: Any) -> Optional[str]:
        """Store one terminal result under its content address; returns
        the spill path (for the journal's ``cache`` record) or None when
        the layer is off / the key is already present. The spill is
        durable before this returns (tmp+fsync+rename), so a journaled
        ``cache`` record never points at a file a crash can lose."""
        if not self.enabled("l3"):
            return None
        if key_digest in self._l3:
            self._l3.move_to_end(key_digest)
            return None
        import numpy as np

        arr = np.asarray(images)
        path = self._l3_path(key_digest)
        tmp = path + ".tmp"
        with open(tmp, "wb") as f:
            np.savez(f, images=arr)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
        self._l3[key_digest] = {"path": path, "images": arr,
                                "bytes": int(arr.nbytes)}
        self._l3_used += int(arr.nbytes)
        self._note("l3", "inserts")
        while self._l3_used > self.l3_bytes and len(self._l3) > 1:
            self._evict_l3(next(iter(self._l3)), "evictions")
        self._m_bytes.labels(layer="l3").set(self._l3_used)
        return path

    def l3_get(self, key_digest: str):
        """The bitwise result for this content key, or None. A seeded
        (journal-replayed) entry loads lazily off its spill; a missing or
        corrupt spill is a silent miss + entry drop, never a fault."""
        if not self.enabled("l3"):
            return None
        entry = self._l3.get(key_digest)
        if entry is None:
            self._note("l3", "misses")
            return None
        if entry["images"] is None:
            import numpy as np

            try:
                with np.load(entry["path"]) as data:
                    entry["images"] = np.asarray(data["images"])
            except Exception:  # noqa: BLE001 — any unreadable spill: miss
                self._note("l3", "corrupt")
                self._note("l3", "misses")
                self._evict_l3(key_digest, None)
                return None
            entry["bytes"] = int(entry["images"].nbytes)
            self._l3_used += entry["bytes"]
            # Seeded loads charge the same budget as inserts: a restart
            # with many journaled entries must not grow residency
            # unbounded on a read-only (hit-heavy) workload. MRU first so
            # the entry being served cannot evict itself.
            self._l3.move_to_end(key_digest)
            while self._l3_used > self.l3_bytes and len(self._l3) > 1:
                self._evict_l3(next(iter(self._l3)), "evictions")
            self._m_bytes.labels(layer="l3").set(self._l3_used)
        self._l3.move_to_end(key_digest)
        self._note("l3", "hits")
        return entry["images"]

    def _evict_l3(self, key_digest: str, count_as: Optional[str]) -> None:
        entry = self._l3.pop(key_digest, None)
        if entry is None:
            return
        self._l3_used -= entry["bytes"]
        try:
            os.remove(entry["path"])
        except OSError:
            pass
        if count_as:
            self._note("l3", count_as)
        self._m_bytes.labels(layer="l3").set(self._l3_used)

    def seed(self, cache_entries: Dict[str, dict]) -> int:
        """Reseed the L3 index from journal-replayed ``cache`` records
        (``ReplayState.cache_entries``): each entry registers path-only
        (lazy load, validated at first hit), and spill files the journal
        does NOT reference are swept — after a crash between an insert's
        spill and its ``cache`` record, the unreferenced file is garbage,
        not evidence. Returns how many entries seeded."""
        if not self.enabled("l3"):
            return 0
        referenced = set()
        n = 0
        for key_digest, rec in cache_entries.items():
            path = rec.get("path")
            if not path or not os.path.exists(path):
                continue
            referenced.add(os.path.abspath(path))
            if key_digest not in self._l3:
                self._l3[key_digest] = {"path": path, "images": None,
                                        "bytes": 0}
                n += 1
        for name in sorted(os.listdir(self.spill_dir)):
            full = os.path.join(self.spill_dir, name)
            if name.startswith("r-") and name.endswith(".npz") and \
                    os.path.abspath(full) not in referenced:
                try:
                    os.remove(full)
                except OSError:
                    pass
        return n

    # -- reporting ---------------------------------------------------------

    def layer_stats(self) -> dict:
        """Per-layer counters + resident bytes — the summary's
        ``semcache.layers`` block and the bench/quality-gate source."""
        out = {}
        for layer in LAYERS:
            if not self.enabled(layer):
                continue
            s = dict(self.stats[layer])
            s["bytes"] = {"l1": self._l1_used,
                          "l2": sum(e["bytes"] for e in self._l2.values()),
                          "l3": self._l3_used}[layer]
            s["entries"] = {"l1": len(self._l1), "l2": len(self._l2),
                            "l3": len(self._l3)}[layer]
            out[layer] = s
        return out
