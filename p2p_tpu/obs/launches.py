"""launches — which compiled programs ran, and their scope index on request.

A TPU trace names a device event by its HLO instruction and its module
(``jit__text2image_jit``) and carries no ``named_scope`` path; the compiled
program's text does (``metadata={op_name=...}``). This registry is how a
reader gets from one to the other for a program that really ran:

- the entry points (``text2image``, ``sweep``, ``sweep_phase1/2``,
  ``encode_prompts``) take a mark before they call their jitted program
  (:func:`built`) and hand the call over after it (:func:`keep_if_built`).
  That costs one integer comparison: whether the compile ledger
  (``utils.cache.CompileLedger``) counted a program built while the call
  ran. Only then — the first launch of a distinct program — the registry
  keeps what is needed to lower that program again: the jitted function,
  its static arguments, and ``ShapeDtypeStruct``s with the arguments'
  shardings. Shapes, never arrays. With them it keeps how each
  self-attention site of the U-Net ran, as the model noted while the
  program was traced (``note_self_site``; ``Launch.self_sites``: per site
  its keys, head width, implementation and the flash kernel's geometry and
  operand dtype), how each transformer block's feed-forward ran
  (``note_ff_site``; ``Launch.ff_sites``: per block its tokens, widths,
  implementation and the GEGLU kernel's tile),
  the bytes of attention maps the controller's store holds
  (``note_store_bytes``; ``Launch.store_bytes``, and the gauge
  ``launch_store_bytes{module}`` of ``obs.metrics``), the U-Net's transformer
  blocks per site group by level (``note_unet_depth``), the chunks the
  decode takes its batch in (``note_decode_chunks``), the denoiser's kind
  (``note_denoiser``) and, for a transformer attending to a masked caption,
  the caption keys each row attends to (``note_caption_keys``). The bytes of the
  weights the program is handed, by part and dtype, are read off the kept
  shapes (``Launch.weights_bytes``).
- :func:`scope_index` lowers, compiles and parses that program lazily, once,
  when somebody asks (``obs.traceparse.scope_index`` on the executable's
  text). After a launch in the same process the executable is still in
  memory, and in a later one the compile is a read of the persistent cache;
  the ledger shows which (``Launch.built_from``). Nothing is lowered,
  compiled or parsed unless asked.

JAX leaves metadata out of the compilation-cache key, so an executable
cached by a tree with other scopes is served with its old ``op_name``s. An
index that finds no scope at all in a program that has them is taken for
such an entry and built once more past the cache (docs/OBSERVABILITY.md,
"Stale scopes").
"""

from __future__ import annotations

import collections
import dataclasses
import inspect
import sys
import time
from typing import Any, Dict, List, Optional, Tuple

from ..utils.cache import compile_ledger
from . import traceparse


_SHORT = {"bfloat16": "bf16", "float32": "f32"}    # as the HLO text has them


@dataclasses.dataclass(frozen=True)
class SelfSite:
    """How one self-attention site of a traced program runs."""

    keys: int                   # pixels = keys of the site
    head_dim: int
    how: str                    # "kernel" | "einsum" | "edited" | "sharded"
    # The flash kernel's tile, where the site reached it: a "kernel" site, or
    # an "edited" one the controller only injects into (ISSUE 37).
    geometry: Optional[Tuple[int, int, int]] = None
    operand: str = ""           # dtype the kernel is handed q, k, v in

    def __str__(self):
        tile = "" if self.geometry is None else " " + "x".join(map(str, self.geometry))
        width = self.operand and " " + _SHORT.get(self.operand, self.operand)
        return f"{self.keys}x{self.head_dim} {self.how}{tile}{width}"


@dataclasses.dataclass(frozen=True)
class FfSite:
    """How one transformer block's GEGLU feed-forward of a traced program
    runs."""

    rows: int                   # tokens: batch × pixels
    channels: int
    inner: int
    how: str                    # "kernel" | "formula" | "sharded"
    tile: Optional[Tuple[int, int]] = None      # the kernel's, where it ran

    def __str__(self):
        tile = "" if self.tile is None else " " + "x".join(map(str, self.tile))
        return f"{self.rows}x{self.channels}x{self.inner} {self.how}{tile}"


@dataclasses.dataclass
class Launch:
    """The first launch of one distinct program, kept abstract."""

    module: str                 # the XLA module's name: ``jit_<function>``
    fn: Any                     # the jitted function
    args: tuple                 # arrays replaced by ShapeDtypeStructs
    kwargs: dict
    index: Optional[Dict[str, str]] = None
    mixed: Optional[Dict[str, Dict[str, int]]] = None
    built_from: str = ""        # "memory" | "cache_hit" | "backend" (compiled)
    # Self-attention sites by layer index: "kernel" / "einsum" (untouched,
    # ``nn.fused_attention``'s two implementations), "edited", "sharded".
    self_sites: Dict[int, SelfSite] = dataclasses.field(default_factory=dict)
    # Feed-forwards by block (keyed by the attention sites called through
    # the block's end, which no other block of the program shares):
    # "kernel" (``kernels.geglu``), "formula" (XLA), "sharded".
    ff_sites: Dict[int, FfSite] = dataclasses.field(default_factory=dict)
    # Bytes of the controller's attention store in this program's carry (per
    # group, where the program runs groups), 0 where it keeps none.
    store_bytes: int = 0
    # Transformer blocks per site group of the U-Net, one int a level (0: the
    # level has no attention); () where the program runs no U-Net.
    unet_depth: Tuple[int, ...] = ()
    # Chunks the autoencoder's decode takes its batch in; 0: no decode.
    decode_chunks: int = 0
    # The denoiser's kind ("unet" | "transformer"); "" where none ran.
    denoiser: str = ""
    # Caption keys each row of the batch attends to under its key mask, of
    # ``caption_len``, where the denoiser attends to a masked caption.
    caption_keys: Tuple[int, ...] = ()
    caption_len: int = 0

    @property
    def weights_bytes(self) -> Dict[str, Dict[str, int]]:
        """Bytes of the weights the program is handed, ``{part: {dtype:
        bytes}}``: a part is a positional argument of the jitted function
        whose name ends in ``_params`` (``unet_params`` is part ``unet``)."""
        import jax

        out = {}
        try:
            names = list(inspect.signature(self.fn).parameters)
        except (TypeError, ValueError):         # not a function with a signature
            return out
        for name, arg in zip(names, self.args):
            if name.endswith("_params"):
                by = collections.Counter()
                for leaf in jax.tree_util.tree_leaves(arg):
                    by[str(leaf.dtype)] += leaf.size * leaf.dtype.itemsize
                out[name[:-len("_params")]] = dict(by)
        return out

    @property
    def self_window_sites(self) -> int:
        """Self sites the controller injects into or reads (``edited``)."""
        return sum(s.how == "edited" for s in self.self_sites.values())

    @property
    def self_site_counts(self) -> Dict[str, int]:
        """``{"kernel": n, "einsum": n, "edited": n}``: sites by how."""
        return dict(collections.Counter(s.how for s in self.self_sites.values()))

    def describe_sites(self) -> str:
        """One line for a log: the counts, then each distinct site shape."""
        shapes = collections.Counter(str(s) for s in self.self_sites.values())
        return (f"self-attention sites {self.self_site_counts}"
                + "".join(f"; {n} of {shape}" for shape, n in shapes.items())
                + f"; controller store {self.store_bytes} bytes")

    def describe_ff(self) -> str:
        """One line for a log: feed-forwards by how, then each distinct
        shape."""
        counts = dict(collections.Counter(s.how for s in self.ff_sites.values()))
        shapes = collections.Counter(str(s) for s in self.ff_sites.values())
        return f"ff {counts}" + "".join(f"; {n} of {shape}"
                                        for shape, n in shapes.items())

    def describe_model(self) -> str:
        """One line for a log: depth by level, decode chunks, weights."""
        parts = self.weights_bytes
        weights = "; ".join(
            f"{part} " + " ".join(f"{_SHORT.get(d, d)}:{n}" for d, n in sorted(by.items()))
            for part, by in parts.items())
        head = ""
        if self.denoiser == "transformer":
            head = (f"denoiser transformer; self window "
                    f"{self.self_window_sites} of {len(self.self_sites)} sites; "
                    f"caption keys {self.caption_keys} of {self.caption_len}; ")
        return (head + f"transformer depth by level {self.unet_depth}; "
                f"decode_chunks {self.decode_chunks}; weights_bytes "
                f"{sum(n for by in parts.values() for n in by.values())}"
                f" ({weights})")

    def _signature(self):
        import jax

        leaves, tree = jax.tree_util.tree_flatten((self.args, self.kwargs))
        return self.module, tree, tuple(
            (x.shape, x.dtype, x.sharding)
            if isinstance(x, jax.ShapeDtypeStruct) else x for x in leaves)


_launches: Dict[str, List[Launch]] = {}     # module -> distinct programs
_traced_sites: Dict[int, SelfSite] = {}     # by site, since the last mark
_traced_ff: Dict[int, FfSite] = {}          # by block, likewise
_traced_store = 0                           # store bytes, since the last mark
_traced_model = {}                          # unet_depth, decode_chunks, likewise


def built() -> int:
    """How many programs the process has built so far, compiled or read from
    the persistent cache: the mark a launch site takes before it calls its
    jitted function. The sites noted from here on are that launch's."""
    global _traced_store
    _traced_sites.clear()
    _traced_ff.clear()
    _traced_model.clear()
    _traced_store = 0
    return compile_ledger().programs


def note_self_site(site: int, how: str, keys: int, head_dim: int,
                   geometry=None, operand: str = "") -> None:
    """Trace time, from the model: self-attention site ``site`` of the
    program being traced, of ``keys`` pixels and heads ``head_dim`` wide,
    runs ``how``, the flash kernel tiled by ``geometry`` and handed q, k, v
    as ``operand`` (a dtype's name). Keyed by site, so a body traced twice
    counts once."""
    _traced_sites[site] = SelfSite(keys, head_dim, how, geometry, operand)


def note_ff_site(block: int, how: str, rows: int, channels: int, inner: int,
                 tile=None) -> None:
    """Trace time, from the model: the feed-forward of transformer block
    ``block`` (the attention sites called through its end) over
    ``rows`` tokens ``channels`` wide, inner width ``inner``, runs ``how``,
    the GEGLU kernel tiled by ``tile``. Keyed by block, so a body traced
    twice counts once."""
    _traced_ff[block] = FfSite(rows, channels, inner, how,
                               None if tile is None else tuple(tile))


def note_store_bytes(n: int) -> None:
    """Trace time, from the sampler: the controller's attention store of the
    program being traced holds ``n`` bytes (the largest, where phases of one
    program each make their own)."""
    global _traced_store
    _traced_store = max(_traced_store, int(n))


def note_unet_depth(depth: Tuple[int, ...]) -> None:
    """Trace time, from the model: the U-Net being traced has ``depth[l]``
    transformer blocks a site group at level ``l``."""
    _traced_model["unet_depth"] = tuple(depth)


def note_denoiser(kind: str) -> None:
    """Trace time, from the samplers' denoiser: its kind."""
    _traced_model["denoiser"] = kind


def note_caption_keys(keys: Tuple[int, ...], length: int) -> None:
    """Host, before the launch: the caption keys each row of its batch
    attends to under the key mask, of ``length``."""
    _traced_model["caption_keys"] = tuple(keys)
    _traced_model["caption_len"] = int(length)


def note_decode_chunks(n: int) -> None:
    """Trace time, from the autoencoder: the decode being traced takes its
    batch in ``n`` chunks."""
    _traced_model["decode_chunks"] = int(n)


def keep_if_built(mark: int, fn, args: tuple, kwargs: dict) -> None:
    """After ``fn(*args, **kwargs)``: where a program was built since
    ``mark`` (traced and compiled, or read from the persistent cache), the
    launch is kept for :func:`scope_index`.

    Two calls around the launch and not a wrapper of it: a wrapper's frame
    would stand under everything JAX traces and lowers in the first launch,
    and how deep that work starts on Python's frame stack decides seconds of
    set-up (PERF.md, Findings, PR 27)."""
    if compile_ledger().programs != mark:
        _keep(fn, args, kwargs)


def _abstract(tree):
    """Arrays to ``ShapeDtypeStruct``s that keep the sharding they were
    committed to; None where the call was itself being traced (there is no
    program of its own)."""
    import jax
    import numpy as np

    traced = False

    def leaf(x):
        nonlocal traced
        if isinstance(x, jax.core.Tracer):
            traced = True
        elif isinstance(x, jax.Array):
            # An uncommitted array lowers with no sharding at all; given its
            # single-device sharding the program would lower, and be cached,
            # as another one.
            return jax.ShapeDtypeStruct(
                x.shape, x.dtype, weak_type=x.weak_type,
                sharding=x.sharding if x.committed else None)
        elif isinstance(x, (np.ndarray, np.generic)):
            return jax.ShapeDtypeStruct(x.shape, x.dtype)
        return x

    out = jax.tree_util.tree_map(leaf, tree)
    return None if traced else out


def _keep(fn, args, kwargs) -> None:
    abstract = _abstract((args, kwargs))
    if abstract is None:
        return
    launch = Launch("jit_" + fn.__name__, fn, *abstract,
                    self_sites=dict(_traced_sites), ff_sites=dict(_traced_ff),
                    store_bytes=_traced_store,
                    **_traced_model)
    known = _launches.setdefault(launch.module, [])
    # Another thread's compile can make a warm call look like a first launch.
    if all(launch._signature() != k._signature() for k in known):
        known.append(launch)
        from . import metrics

        metrics.registry().gauge(
            "launch_store_bytes", "bytes of attention maps the controller's "
            "store holds in the newest program of a module",
            labels=("module",)).labels(module=launch.module).set(launch.store_bytes)


def programs(module: Optional[str] = None) -> List[Launch]:
    """The distinct programs launched so far, of ``module`` or of all."""
    if module is not None:
        return list(_launches.get(module, ()))
    return [p for ps in _launches.values() for p in ps]


def scope_index(module: str
                ) -> Optional[Tuple[Dict[str, str], Dict[str, Dict[str, int]]]]:
    """``obs.traceparse.scope_index`` of the newest program of ``module``,
    the one whose first launch came last: ``({HLO instruction: scope},
    {fusion: {scope: members}})``, built on the first request and kept. None
    where no such program was launched. A trace names a program by its
    module only, and instruction names differ between two programs of one
    module: where an older program of the module ran again in the traced
    window (``len(programs(module)) > 1``), this index is not that
    program's, and a reader sees it as a low share of scoped time."""
    known = _launches.get(module)
    if not known:
        return None
    launch = known[-1]
    if launch.index is None:
        _build(launch)
    return launch.index, launch.mixed


#: A compile option that changes logging only, and with it the key under
#: which JAX caches the executable: how a program is compiled past a stale
#: entry, in memory and on disk, and found again by the next process that asks.
_PAST_THE_CACHE = {"xla_detailed_logging": True}


def _build(launch: Launch) -> None:
    index, mixed = _compile_and_parse(launch)
    if not index and launch.built_from != "backend":
        # Every program launched from here has scopes, so a cached
        # executable without one was compiled before they were named (the
        # cache's key leaves metadata out). Once more, past the cache.
        index, mixed = _compile_and_parse(launch, _PAST_THE_CACHE)
    launch.index, launch.mixed = index, mixed
    # Whoever asked prints the scope tree (a traced run); this goes with it.
    print(f"launch {launch.module}: {len(index)} instructions from "
          f"{launch.built_from}; {launch.describe_sites()}; "
          f"{launch.describe_ff()}; {launch.describe_model()}", file=sys.stderr)


def _compile_and_parse(launch: Launch, compiler_options=None):
    t0 = time.monotonic()
    lowered = launch.fn.lower(*launch.args, **launch.kwargs)
    text = lowered.compile(compiler_options=compiler_options).as_text()
    # No row: the executable the launch itself built, still in memory.
    rows = compile_ledger().rows("backend", "cache_hit", since=t0)
    launch.built_from = rows[-1].kind if rows else "memory"
    return traceparse.scope_index(text)
