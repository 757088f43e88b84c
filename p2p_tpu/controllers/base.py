"""Functional attention-controller core.

The reference's deep idea is a *pure function* from (attention probabilities,
layer position, step) to attention probabilities, plus a latent post-step hook,
with every edit parameter precomputed host-side (`/root/reference/main.py:69-290`).
Its implementation, however, is stateful: runtime monkey-patching installs a
hook (`/root/reference/ptp_utils.py:175-242`) and `cur_step`/`cur_att_layer`
counters plus a dict-of-lists attention store carry the bookkeeping
(`/root/reference/main.py:85-159`).

Here that becomes explicit functional state:

- **Layer position is static.** Each attention call site in our U-Net knows
  its :class:`AttnMeta` at trace time (place / is_cross / resolution /
  store slot), replacing the runtime registration walk and the
  ``cur_att_layer`` counter.
- **The step index is threaded by ``lax.scan``** — no ``cur_step`` mutation.
- **The store is a tuple of fixed-shape arrays** (one per stored layer),
  accumulated by addition across steps — replacing the growing
  ``{down,mid,up}_{cross,self}`` lists (`/root/reference/main.py:118-142`).
  A layer is stored only for a reader (``AttnLayout.for_readers``): the
  caller that takes the store back, or LocalBlend.
- **Controllers are pytrees** (`flax.struct`) passed as arguments into the
  jitted sampling loop; an "empty" controller compiles away to the identity,
  making `EmptyControl ≡ no controller` true at the XLA-program level.

Attention tensors here have shape ``(2B, heads, P, K)`` — the full
classifier-free-guidance batch ``[uncond(B); cond(B)]`` with ``B = 1 + E``
(source prompt + E edit prompts). Edits touch only the conditional half, and
within it only rows ``1:`` (the edit prompts), exactly as
`/root/reference/main.py:90-92,187` does.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from flax import struct

from .edit import EditParams, edit_cross_attention, edit_self_attention
from .blend import BlendParams, apply_local_blend


@dataclasses.dataclass(frozen=True)
class AttnMeta:
    """Static description of one attention call site inside the U-Net.

    Replaces the runtime layer walk + counting of
    `/root/reference/ptp_utils.py:223-242`: the structure is known at trace
    time, so layer bookkeeping costs nothing in the compiled program.
    """

    layer_idx: int          # global index over all attention call sites
    place: str              # 'down' | 'mid' | 'up'; 'block' in a transformer
    is_cross: bool
    resolution: int         # spatial side length of the feature map (pixels = resolution²)
    heads: int
    key_len: int            # K (= 77 for cross, = resolution² for self)
    store_slot: Optional[int] = None  # index into the store state, or None
    # Feature-map channel count at this site (= the attention output width).
    # 0 in hand-built layouts that predate it; required (> 0) only by the
    # phase-2 cross-attention cache, which needs output shapes up front.
    channels: int = 0

    @property
    def pixels(self) -> int:
        return self.resolution * self.resolution


@dataclasses.dataclass(frozen=True)
class StoreConfig:
    """What the attention store keeps.

    The reference always stores every ≤32²-pixel map
    (`/root/reference/main.py:131`); we additionally allow switching off
    self/cross storage independently so edit-only runs (which need just the
    16×16 cross maps for LocalBlend) don't pay ~300MB of self-attention
    accumulation bandwidth.
    """

    max_pixels: int = 32 * 32
    store_cross: bool = True
    store_self: bool = True

    def wants(self, meta: "AttnMeta") -> bool:
        if meta.pixels > self.max_pixels:
            return False
        return self.store_cross if meta.is_cross else self.store_self


#: The map side the paper's code writes as a literal (`/root/reference/main.py`:
#: LocalBlend's 16² cross maps, the 16² self-injection bound); the 32² bounds
#: (the store, null-text's self window) stand one level above it.
PAPER_RESOLUTION = 16


@dataclasses.dataclass(frozen=True)
class PaperLevel:
    """A map side nobody gave: the paper's literal for SD-1.4's pyramid (16;
    ``up=1``: its 32), standing in ``EditParams.self_max_pixels`` and
    ``BlendParams.resolution`` until the controller meets a model and
    ``AttnLayout.resolve`` takes it against that model's levels. It orders
    against nothing: a controller that reaches a trace unresolved is a
    ``TypeError`` there, not SD-1.4's sites picked on another pyramid."""

    up: int = 0


@dataclasses.dataclass(frozen=True)
class AttnLayout:
    """The full static attention structure of a model: one AttnMeta per call
    site, with store slots assigned. Built once per (model, StoreConfig)."""

    metas: Tuple[AttnMeta, ...]
    store_cfg: StoreConfig
    # The latent's side, where the layout was built from a model's
    # configuration (its top level may hold no attention site); None for a
    # hand-built layout, which stands for its largest site's.
    latent_size: Optional[int] = None

    @property
    def denoiser(self) -> str:
        """``"transformer"`` where every site is a transformer denoiser's
        block (place ``"block"``, all at one token grid), else ``"unet"``."""
        if self.metas and all(m.place == "block" for m in self.metas):
            return "transformer"
        return "unet"

    @property
    def num_store_slots(self) -> int:
        return sum(1 for m in self.metas if m.store_slot is not None)

    def stored_metas(self) -> Tuple[AttnMeta, ...]:
        return tuple(m for m in self.metas if m.store_slot is not None)

    def edit_resolution(self) -> int:
        """The side of the attention maps the paper's defaults stand for in
        this model: 16 (the controllers' self window ``16²``, LocalBlend's
        maps) wherever the pyramid has a 16² level, else the level that
        stands where 16² stands in SD-1.4's 64/32/16/8, a quarter of the
        latent's side (24 of SD-2.1's 96/48/24/12, 32 of SDXL's 128, whose
        attention levels are 64/32). A model with neither is an error here,
        at controller build time, never an empty site list. A transformer
        denoiser's sites (place ``"block"``) all stand at its token grid,
        and its defaults take that one resolution: the self window holds
        every self site, LocalBlend's side is the grid's."""
        sides = sorted({m.resolution for m in self.metas})
        if PAPER_RESOLUTION in sides:
            return PAPER_RESOLUTION
        if self.denoiser == "transformer":
            return sides[0]
        level = (self.latent_size or sides[-1]) // 4
        if level not in sides:
            raise ValueError(
                f"no default edit resolution: the model's attention levels "
                f"{sides} hold neither {PAPER_RESOLUTION} nor a quarter of "
                f"the latent's side ({level}); pass the resolution explicitly")
        return level

    def resolve(self, controller: Optional["Controller"]) -> Optional["Controller"]:
        """Take the resolutions nobody gave (``PaperLevel``) against this
        model: the one place a default becomes a number, called where a
        controller meets a pipeline (``text2image``, ``sweep`` and the phase
        pools, ``serve.request.prepare``), before anything is traced. The
        self bound becomes the square of ``edit_resolution()`` raised
        ``up`` levels (16² / 32² wherever the pyramid has a 16² level, 24² /
        48² on SD-2.1); LocalBlend's side likewise, and a side, given or
        not, at which the model stores no cross map is refused here rather
        than while tracing. A controller with nothing to resolve comes back
        as it is, stacked (``sweep``) or not: only static fields change."""
        if controller is None:
            return None
        edit, blend = controller.edit, controller.blend
        if edit is not None and isinstance(edit.self_max_pixels, PaperLevel):
            side = self.edit_resolution() << edit.self_max_pixels.up
            edit = edit.replace(self_max_pixels=side * side)
        if blend is not None:
            if self.denoiser != "unet":
                raise ValueError(f"LocalBlend: not supported for a "
                                 f"{self.denoiser} denoiser (U-Net only)")
            if isinstance(blend.resolution, PaperLevel):
                blend = blend.replace(
                    resolution=self.edit_resolution() << blend.resolution.up)
            if not self.blend_metas(blend.resolution):
                stored = sorted({m.resolution for m in self.stored_metas() if m.is_cross})
                raise ValueError(
                    f"LocalBlend resolution {blend.resolution}: the model stores "
                    f"no cross-attention map of that side (stored cross levels "
                    f"{stored})")
        if edit is controller.edit and blend is controller.blend:
            return controller
        return controller.replace(edit=edit, blend=blend)

    def for_readers(self, controller: Optional["Controller"],
                    return_store: bool = False) -> "AttnLayout":
        """This layout with store slots at the sites somebody reads, and
        nowhere else: called beside ``resolve``, where a controller, a
        layout and the caller's ``return_store`` meet, before anything is
        traced. A stored map has two kinds of reader. The caller that takes
        the store back (``text2image(return_store=True)``) reads every slot
        of the ``StoreConfig``: the layout comes back as it is. LocalBlend
        reads the cross maps of ``blend_metas(blend.resolution)`` and
        nothing else: without ``return_store`` only those keep a slot
        (renumbered from 0, so the state tuple holds just them). With
        neither, no site has a slot, the state is ``()``, and a self site
        above the edit window is untouched (``controller_touches``) and
        runs fused, whatever ``controller.store`` says: a map nobody reads
        is not worth a (B, heads, P, P) tensor in HBM at every step."""
        if return_store:
            return self
        blend = None if controller is None else controller.blend
        read = () if blend is None else self.blend_metas(blend.resolution)
        slots = {m.layer_idx: i for i, m in enumerate(read)}
        return AttnLayout(
            tuple(dataclasses.replace(m, store_slot=slots.get(m.layer_idx))
                  for m in self.metas),
            self.store_cfg, self.latent_size)

    def blend_metas(self, resolution: int = 16) -> Tuple[AttnMeta, ...]:
        """The cross-attention maps LocalBlend consumes — all cross sites at
        ``resolution`` (for SD-1.4 this is exactly the reference's
        ``down_cross[2:4] + up_cross[:3]`` slice, `/root/reference/main.py:37-38`,
        but derived from the model rather than hard-coded)."""
        return tuple(
            m for m in self.metas
            if m.is_cross and m.resolution == resolution and m.store_slot is not None
        )


def build_layout(
    specs: Sequence[Tuple],
    store_cfg: StoreConfig = StoreConfig(),
    latent_size: Optional[int] = None,
) -> AttnLayout:
    """Assemble an :class:`AttnLayout` from ``(place, is_cross, resolution,
    heads, key_len[, channels])`` tuples in call order, assigning store slots
    to the sites the :class:`StoreConfig` wants. The optional 6th element is
    the site's feature-map channel count (needed by the phase-2 attention
    cache); 5-tuples remain valid and get ``channels=0``. ``latent_size`` is
    the model's latent side (``AttnLayout.latent_size``)."""
    metas = []
    slot = 0
    for idx, spec in enumerate(specs):
        place, is_cross, resolution, heads, key_len = spec[:5]
        channels = spec[5] if len(spec) > 5 else 0
        meta = AttnMeta(idx, place, is_cross, resolution, heads, key_len,
                        channels=channels)
        if store_cfg.wants(meta):
            meta = dataclasses.replace(meta, store_slot=slot)
            slot += 1
        metas.append(meta)
    return AttnLayout(tuple(metas), store_cfg, latent_size)


@struct.dataclass
class Controller:
    """A prompt-to-prompt controller as a pytree.

    ``edit``/``blend`` are parameter pytrees (or None); the remaining fields
    are static. The all-None controller is the identity (EmptyControl,
    `/root/reference/main.py:110-113`); ``store=True`` alone reproduces
    AttentionStore; ``spatial_stop_inject`` reproduces SpatialReplace
    (`/root/reference/null_text.py:158-168`).
    """

    edit: Optional[EditParams] = None
    blend: Optional[BlendParams] = None
    # Scalar leaf (traced) when present, so the injection horizon can sweep
    # without recompiling; None disables the SpatialReplace path statically.
    spatial_stop_inject: Optional[jax.Array] = None
    store: bool = struct.field(pytree_node=False, default=False)

    @property
    def is_identity(self) -> bool:
        return (
            self.edit is None
            and self.blend is None
            and not self.store
            and self.spatial_stop_inject is None
        )

    @property
    def needs_store(self) -> bool:
        """Does this controller accumulate maps at the layout's store slots?
        Which sites have a slot is the layout's matter, and follows from who
        reads the store (``AttnLayout.for_readers``): under ``store=True``
        with no reader there is none, and the state is ``()``."""
        return self.store or self.blend is not None


def controller_touches(controller: Optional["Controller"], meta: AttnMeta) -> bool:
    """Static (trace-time) predicate: does this controller ever read or write
    this call site's attention probabilities?

    Sites where this is False run fully fused attention — the probability
    tensor never exists in the compiled program. This is the TPU answer to the
    reference disabling xformers globally (`/root/reference/null_text.py:32-35`):
    only the sites prompt-to-prompt provably touches (edited self maps ≤
    ``self_max_pixels``, all cross maps under an edit, and stored slots —
    `/root/reference/main.py:131,170`) pay for materialization. A site has a
    slot only where the store has a reader (``AttnLayout.for_readers``), so
    ``store=True`` alone touches nothing: a self site above the edit window
    whose map nobody takes back runs fused.
    """
    if controller is None or controller.is_identity:
        return False
    if meta.store_slot is not None and controller.needs_store:
        return True
    if controller.edit is not None:
        if meta.is_cross:
            return True
        return meta.pixels <= controller.edit.self_max_pixels
    return False


def controller_only_injects(controller: Optional["Controller"],
                            meta: AttnMeta) -> bool:
    """Static: the controller touches this site only to inject the source
    prompt's self map into the edit rows (``edit_self_attention``): a self
    site within ``self_max_pixels`` whose map nobody stores. Such a site
    needs no probabilities: ``inject_self_operands`` gives attention the
    base row's q and k in the edit rows, and where the flash kernel takes
    its shape the model runs it there (``models/unet.py``)."""
    return (controller_touches(controller, meta) and not meta.is_cross
            and not (meta.store_slot is not None and controller.needs_store))


def controller_step_window(controller: Optional["Controller"],
                           num_steps: int) -> int:
    """Host-side: the last scan step (exclusive) at which this controller can
    still *modify* the trajectory through its attention hooks — the max over
    the cross-replace schedule's support, the self-injection window end, and
    the SpatialReplace injection horizon.

    This is the floor for phase-gated sampling's ``gate='auto'``: truncating
    CFG/cross-attention before this step would cut inside an active edit
    window and change P2P semantics, so the auto gate never resolves below
    it. Reads concrete (host-side) controller leaves — controllers are built
    host-side, so calling this on traced values is a usage error. Leaves
    stacked with a leading sweep/group axis (``parallel.sweep``) are handled:
    the window is the max over the stacked controllers.

    ``needs_store`` guard: a LocalBlend past this window keeps compositing
    latents in phase 2 from the *frozen* phase-1 store (accumulation stops at
    the gate — the maps it masks with are the phase-1 average, which is also
    what the reference's late steps are dominated by); an explicit
    ``store=True`` (observability) controller under-accumulates when gated —
    the engine warns rather than errors, since stores don't alter sampling.
    """
    if controller is None or controller.is_identity:
        return 0
    import numpy as np

    end = 0
    if controller.edit is not None:
        ca = np.asarray(controller.edit.cross_alpha)
        # cross_alpha is (T+1, E, 1, 1, L), or (G, T+1, ...) when stacked for
        # a sweep: the step axis is ndim-5. Support of the blend schedule =
        # steps where any token still draws from the transformed base.
        step_axis = ca.ndim - 5
        other = tuple(i for i in range(ca.ndim) if i != step_axis)
        nz = np.nonzero(np.any(ca != 0, axis=other))[0]
        if nz.size:
            end = max(end, int(nz[-1]) + 1)
        end = max(end, int(np.max(np.asarray(controller.edit.self_end))))
    if controller.spatial_stop_inject is not None:
        end = max(end, int(np.max(np.asarray(controller.spatial_stop_inject))))
    return min(end, num_steps)


def controller_edit_windows(controller: Optional["Controller"],
                            num_steps: int) -> Tuple[int, int]:
    """Host-side: the per-kind edit-window ends ``(cross_end, self_end)``
    — the last scan step (exclusive) at which the controller can still
    modify CROSS-attention maps vs SELF-attention maps.

    :func:`controller_step_window` is the max of these (plus the
    SpatialReplace horizon, which is a latent-space hook and constrains
    neither attention kind); the per-site reuse-schedule conflict check
    (``engine.reuse.warn_schedule_conflicts``) needs the split so a
    self-site reuse inside only the *cross* window doesn't warn."""
    if controller is None or controller.is_identity \
            or controller.edit is None:
        return 0, 0
    import numpy as np

    ca = np.asarray(controller.edit.cross_alpha)
    step_axis = ca.ndim - 5
    other = tuple(i for i in range(ca.ndim) if i != step_axis)
    nz = np.nonzero(np.any(ca != 0, axis=other))[0]
    cross_end = int(nz[-1]) + 1 if nz.size else 0
    self_end = int(np.max(np.asarray(controller.edit.self_end)))
    return min(cross_end, num_steps), min(self_end, num_steps)


StoreState = Tuple[jax.Array, ...]


def init_store_state(
    layout: AttnLayout, batch_cond: int, dtype=jnp.float32
) -> StoreState:
    """Zero-initialized accumulation buffers, one per stored call site:
    ``(B_cond, heads, pixels, key_len)`` each. Fixed shapes — the jit-friendly
    replacement for `/root/reference/main.py:118-127`'s dict of lists. The
    stored sites are ``layout``'s slots: pass the layout the program is
    traced with (``AttnLayout.for_readers``), or state and program disagree."""
    return tuple(
        jnp.zeros((batch_cond, m.heads, m.pixels, m.key_len), dtype=dtype)
        for m in layout.stored_metas()
    )


def empty_store_state() -> StoreState:
    return ()


def apply_attention_control(
    controller: Optional[Controller],
    meta: AttnMeta,
    state: StoreState,
    attn: jax.Array,
    step: jax.Array,
) -> Tuple[StoreState, jax.Array]:
    """The per-layer hook: edit the conditional half, then store the
    *post-edit* maps.

    ``attn``: softmax probabilities, shape ``(2B, heads, P, K)``. Mirrors the
    call path `/root/reference/main.py:85-98` → `main.py:180-197`. Ordering
    note: the reference *appears* to store before editing
    (`main.py:181` calls the store superclass first), but it appends the
    cond-half tensor **by reference** and then mutates it in place
    (`main.py:186,193` write through a reshape view of the same storage) —
    so what its store, LocalBlend, and visualizations actually see is the
    edited attention for rows 1:. We reproduce that observable behavior
    explicitly: edit first, store the result. Everything branching on
    ``meta`` or controller structure is static, so the identity controller
    adds zero ops to the compiled program.
    """
    if controller is None or controller.is_identity:
        return state, attn

    two_b = attn.shape[0]
    b = two_b // 2
    cond = attn[b:]

    if controller.edit is not None and b > 1:
        base, edits = cond[0], cond[1:]
        if meta.is_cross:
            new_edits = edit_cross_attention(controller.edit, base, edits, step)
        else:
            new_edits = edit_self_attention(controller.edit, base, edits, step, meta.pixels)
        cond = jnp.concatenate([base[None], new_edits.astype(attn.dtype)], axis=0)
        attn = jnp.concatenate([attn[:b], cond], axis=0)

    if meta.store_slot is not None and controller.needs_store:
        lst = list(state)
        lst[meta.store_slot] = lst[meta.store_slot] + cond.astype(lst[meta.store_slot].dtype)
        state = tuple(lst)

    return state, attn


def apply_step_callback(
    controller: Optional[Controller],
    layout: AttnLayout,
    state: StoreState,
    x_t: jax.Array,
    step: jax.Array,
) -> jax.Array:
    """Post-scheduler-step latent hook: SpatialReplace injection and/or
    LocalBlend compositing (`/root/reference/main.py:164-167`,
    `/root/reference/null_text.py:158-168`)."""
    if controller is None or controller.is_identity:
        return x_t

    if controller.spatial_stop_inject is not None:
        injected = jnp.broadcast_to(x_t[:1], x_t.shape)
        x_t = jnp.where(step < controller.spatial_stop_inject, injected, x_t)

    if controller.blend is not None:
        x_t = apply_local_blend(controller.blend, layout, state, x_t, step)

    return x_t


def average_attention(
    layout: AttnLayout, state: StoreState, num_steps: int
) -> dict:
    """Average stored maps over steps, returned as the reference's
    ``{place}_{kind}`` dict of lists (`/root/reference/main.py:144-149`) for
    the visualization layer."""
    out: dict = {
        "down_cross": [], "mid_cross": [], "up_cross": [],
        "down_self": [], "mid_self": [], "up_self": [],
    }
    for m in layout.stored_metas():
        key = f"{m.place}_{'cross' if m.is_cross else 'self'}"
        out[key].append(state[m.store_slot] / num_steps)
    return out
