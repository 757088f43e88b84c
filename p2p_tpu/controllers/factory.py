"""Controller constructors — the user-facing edit API.

These mirror the reference's controller class constructors
(`/root/reference/main.py:215-278`) and its `make_controller` factory
(`/root/reference/null_text.py:369-401`, with its `blend_word` NameError bug
fixed by design), but produce immutable :class:`Controller` pytrees whose
parameters were precomputed host-side.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional, Sequence, Tuple, Union

import jax.numpy as jnp
import numpy as np

from ..align.aligner import get_refinement_mapper, get_replacement_mapper
from ..align.words import Bounds, get_equalizer, get_time_words_attention_alpha, get_word_inds
from ..obs.spans import span
from ..utils.tokenizer import Tokenizer
from .base import Controller, PaperLevel
from .blend import BlendParams
from .edit import EditParams

CrossSteps = Union[Bounds, Dict[str, Bounds]]


def _self_window(num_steps: int, self_replace_steps: Union[float, Tuple[float, float]]
                 ) -> Tuple[int, int]:
    """Float → (0, v) window, scaled to step counts (`/root/reference/main.py:208-211`)."""
    if isinstance(self_replace_steps, (int, float)):
        self_replace_steps = (0.0, float(self_replace_steps))
    return int(num_steps * self_replace_steps[0]), int(num_steps * self_replace_steps[1])


def _cross_alpha(prompts, num_steps, cross_replace_steps, tokenizer, max_len):
    return jnp.asarray(
        get_time_words_attention_alpha(prompts, num_steps, cross_replace_steps,
                                       tokenizer, max_num_words=max_len)
    )


def _or_paper(given: Optional[int], up: int = 0):
    """A resolution as given, else the paper's literal as a ``PaperLevel``
    (16; ``up=1``: 32), which ``AttnLayout.resolve`` takes against the model
    where the controller meets it: 16 wherever the pyramid has a 16² level,
    24 on SD-2.1's 96² latent."""
    return PaperLevel(up) if given is None else given


def _entry_span(make):
    """``entry.controller``: the host work of building an edit's controller
    (alignment, word indices, the arrays put on the device). A root span of
    its own, since the caller builds the controller before the sampling
    call."""
    @functools.wraps(make)
    def spanned(prompts, num_steps, *args, **kwargs):
        with span("entry.controller", kind=make.__name__,
                  prompts=len(prompts), steps=int(num_steps)):
            return make(prompts, num_steps, *args, **kwargs)

    return spanned


def empty_control() -> Controller:
    """Identity controller (`/root/reference/main.py:110-113`) — compiles away."""
    return Controller()


def attention_store() -> Controller:
    """Store-only controller (`/root/reference/main.py:116-159`)."""
    return Controller(store=True)


def spatial_replace(num_steps: int, stop_inject: float) -> Controller:
    """Latent injection for the first ``(1-stop_inject)·T`` steps
    (`/root/reference/null_text.py:158-168`)."""
    return Controller(spatial_stop_inject=jnp.int32(int((1 - stop_inject) * num_steps)))


def local_blend(
    prompts: Sequence[str],
    words: Sequence[Union[str, Sequence[str]]],
    tokenizer: Tokenizer,
    substruct_words: Optional[Sequence[Union[str, Sequence[str]]]] = None,
    start_blend: float = 0.0,
    num_steps: int = 50,
    th: Tuple[float, float] = (0.3, 0.3),
    resolution: Optional[int] = None,
    max_len: Optional[int] = None,
) -> BlendParams:
    """Build LocalBlend word masks (`/root/reference/main.py:54-66`,
    `/root/reference/null_text.py:72-102`). ``start_blend`` is a fraction of
    ``num_steps`` as in `/root/reference/null_text.py:100`. ``resolution``
    defaults to the model's level in the place of the paper's 16
    (``_or_paper``); a side at which the model stores no cross-attention map
    is refused by ``AttnLayout.resolve``, before anything is traced."""
    L = max_len or tokenizer.model_max_length

    def one_hot(word_lists) -> np.ndarray:
        alpha = np.zeros((len(prompts), L), dtype=np.float32)
        for i, (prompt, ws) in enumerate(zip(prompts, word_lists)):
            if isinstance(ws, str):
                ws = [ws]
            for w in ws:
                alpha[i, get_word_inds(prompt, w, tokenizer)] = 1.0
        return alpha

    return BlendParams(
        alpha_layers=jnp.asarray(one_hot(words)),
        substruct_layers=(jnp.asarray(one_hot(substruct_words))
                          if substruct_words is not None else None),
        start_blend=jnp.int32(int(start_blend * num_steps)),
        th_pool=jnp.float32(th[0]),
        th_nopool=jnp.float32(th[1]),
        resolution=_or_paper(resolution),
    )


@_entry_span
def attention_replace(
    prompts: Sequence[str],
    num_steps: int,
    cross_replace_steps: CrossSteps,
    self_replace_steps: Union[float, Tuple[float, float]],
    tokenizer: Tokenizer,
    local_blend: Optional[BlendParams] = None,
    self_max_pixels: Optional[int] = None,
    max_len: Optional[int] = None,
    store: bool = True,
) -> Controller:
    """Word-swap edit (`/root/reference/main.py:215-230`).

    ``store=True`` mirrors the reference, whose edit controllers extend
    AttentionStore and always accumulate ≤32²-pixel maps (`main.py:162`).
    Here the maps are accumulated for a reader only
    (``AttnLayout.for_readers``): ask for them with
    ``text2image(return_store=True)``; otherwise the flag costs nothing.
    ``self_max_pixels`` defaults to the paper's 16² (`main.py:170`), taken
    against the model where the controller meets it (``_or_paper``: SD-2.1's
    24²)."""
    L = max_len or tokenizer.model_max_length
    lo, hi = _self_window(num_steps, self_replace_steps)
    edit = EditParams(
        cross_alpha=_cross_alpha(prompts, num_steps, cross_replace_steps, tokenizer, L),
        mapper=jnp.asarray(get_replacement_mapper(prompts, tokenizer, max_len=L)),
        kind="replace",
        self_start=jnp.int32(lo),
        self_end=jnp.int32(hi),
        self_max_pixels=_or_paper(self_max_pixels),
    )
    return Controller(edit=edit, blend=local_blend, store=store)


@_entry_span
def attention_refine(
    prompts: Sequence[str],
    num_steps: int,
    cross_replace_steps: CrossSteps,
    self_replace_steps: Union[float, Tuple[float, float]],
    tokenizer: Tokenizer,
    local_blend: Optional[BlendParams] = None,
    self_max_pixels: Optional[int] = None,
    max_len: Optional[int] = None,
    store: bool = True,
) -> Controller:
    """Token-add edit via NW alignment (`/root/reference/main.py:233-253`)."""
    L = max_len or tokenizer.model_max_length
    mapper, alphas = get_refinement_mapper(prompts, tokenizer, max_len=L)
    lo, hi = _self_window(num_steps, self_replace_steps)
    edit = EditParams(
        cross_alpha=_cross_alpha(prompts, num_steps, cross_replace_steps, tokenizer, L),
        mapper=jnp.asarray(mapper),
        refine_alphas=jnp.asarray(alphas)[:, None, None, :],
        kind="refine",
        self_start=jnp.int32(lo),
        self_end=jnp.int32(hi),
        self_max_pixels=_or_paper(self_max_pixels),
    )
    return Controller(edit=edit, blend=local_blend, store=store)


@_entry_span
def attention_reweight(
    prompts: Sequence[str],
    num_steps: int,
    cross_replace_steps: CrossSteps,
    self_replace_steps: Union[float, Tuple[float, float]],
    equalizer: Union[np.ndarray, "jnp.ndarray"],
    tokenizer: Tokenizer,
    local_blend: Optional[BlendParams] = None,
    base: Optional[Controller] = None,
    self_max_pixels: Optional[int] = None,
    max_len: Optional[int] = None,
    store: bool = True,
) -> Controller:
    """Per-token attention rescaling, optionally stacked on a Replace/Refine
    controller (`/root/reference/main.py:256-278`): ``base``'s cross transform
    runs first, exactly like the reference's ``prev_controller`` chaining."""
    L = max_len or tokenizer.model_max_length
    lo, hi = _self_window(num_steps, self_replace_steps)
    eq = jnp.asarray(equalizer)
    if base is not None and base.edit is not None:
        kind = base.edit.kind
        mapper = base.edit.mapper
        refine_alphas = base.edit.refine_alphas
        if base.edit.equalizer is not None:
            # Reweight-on-Reweight: the reference's prev_controller recursion
            # applies both equalizers (`/root/reference/main.py:258-263`);
            # per-token scales compose multiplicatively.
            eq = eq * base.edit.equalizer
    else:
        kind, mapper, refine_alphas = "none", None, None
    if base is not None and local_blend is None:
        local_blend = base.blend
    edit = EditParams(
        cross_alpha=_cross_alpha(prompts, num_steps, cross_replace_steps, tokenizer, L),
        mapper=mapper,
        refine_alphas=refine_alphas,
        equalizer=eq,
        kind=kind,
        self_start=jnp.int32(lo),
        self_end=jnp.int32(hi),
        self_max_pixels=_or_paper(self_max_pixels),
    )
    return Controller(edit=edit, blend=local_blend, store=store)


def make_controller(
    prompts: Sequence[str],
    is_replace_controller: bool,
    cross_replace_steps: CrossSteps,
    self_replace_steps: Union[float, Tuple[float, float]],
    tokenizer: Tokenizer,
    num_steps: int = 50,
    blend_words=None,
    equalizer_params: Optional[dict] = None,
    self_max_pixels: Optional[int] = None,
    blend_resolution: Optional[int] = None,
) -> Controller:
    """One-call controller assembly (`/root/reference/null_text.py:369-401`).

    Defaults follow the null-text variant (``self_max_pixels=32²``,
    LocalBlend on the 16² maps with 0.2 start warm-up), each taken against
    the model where the controller meets it (``_or_paper``: 48² and 24 on
    SD-2.1's 96² latent, which has no 16² level). ``equalizer_params`` =
    ``{"words": ..., "values": ...}`` adds a Reweight stage on top.
    """
    self_max_pixels = _or_paper(self_max_pixels, up=1)
    lb = None
    if blend_words is not None:
        lb = local_blend(prompts, blend_words, tokenizer,
                         start_blend=0.2, num_steps=num_steps,
                         resolution=blend_resolution)
    maker = attention_replace if is_replace_controller else attention_refine
    controller = maker(prompts, num_steps, cross_replace_steps, self_replace_steps,
                       tokenizer, local_blend=lb, self_max_pixels=self_max_pixels)
    if equalizer_params is not None:
        eq = get_equalizer(prompts[1], equalizer_params["words"],
                           equalizer_params["values"], tokenizer, mode="paired")
        controller = attention_reweight(
            prompts, num_steps, cross_replace_steps, self_replace_steps, eq,
            tokenizer, local_blend=lb, base=controller,
            self_max_pixels=self_max_pixels,
        )
    return controller
