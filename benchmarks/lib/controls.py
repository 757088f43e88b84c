"""The control and the planted faults of the output check, as context
managers that put a broken or lower-precision path in the program's place.
The benchmark's own runs never use them: ``tools/readings.py`` reads them on
the chip at the cells' own sizes, and the tests under ``tests/benchmark`` see
``correct`` come out false with each of them at a toy size.

The control is the program's own lower-precision path: bfloat16 arrays, the
nearest precision below the float32 the configurations state, through the
``dtype`` argument of ``text2image`` and through the dtype of the arrays
handed to ``sweep``: the floating leaves of whatever the conditioning is (an
array, or a tree of context, pooled text and a caption's integer key mask,
which keeps its type).
"""

from __future__ import annotations

import contextlib
import importlib


@contextlib.contextmanager
def _installed(text2image=None, sweep=None, phase1=None, phase2=None):
    """Put wrappers around the program's entries for the length of a
    ``with``: ``engine.sampler.text2image``, ``parallel.sweep`` (under both
    names the program imports it by), and the serve pools' ``sweep_phase1``
    and ``sweep_phase2``. Each wrapper takes the original and returns what
    stands in for it."""
    sampler = importlib.import_module("p2p_tpu.engine.sampler")
    sweep_mod = importlib.import_module("p2p_tpu.parallel.sweep")
    package = importlib.import_module("p2p_tpu.parallel")
    places = [(sampler, "text2image", text2image), (sweep_mod, "sweep", sweep),
              (package, "sweep", sweep), (sweep_mod, "sweep_phase1", phase1),
              (sweep_mod, "sweep_phase2", phase2)]
    saved = [(m, n, getattr(m, n)) for m, n, w in places if w is not None]
    try:
        for m, n, w in places:
            if w is not None:
                setattr(m, n, w(getattr(m, n)))
        yield
    finally:
        for m, n, original in saved:
            setattr(m, n, original)


def narrowed(tree):
    """``tree`` with its floating leaves in bfloat16, the others as they are."""
    import jax
    import jax.numpy as jnp

    return jax.tree.map(lambda x: x.astype(jnp.bfloat16)
                        if jnp.issubdtype(x.dtype, jnp.floating) else x, tree)


def bfloat16():
    """The control: every entry the drivers call runs in bfloat16."""
    import jax.numpy as jnp

    def t2i(orig):
        return lambda *a, **k: orig(*a, **dict(k, dtype=jnp.bfloat16))

    def sweep(orig):
        def run(pipe, context, latents, *a, **k):
            images, final = orig(pipe, narrowed(context), narrowed(latents), *a, **k)
            return images, final.astype(jnp.float32)
        return run

    def phase1(orig):
        return lambda pipe, context, latents, *a, **k: orig(
            pipe, narrowed(context), narrowed(latents), *a, **k)

    def phase2(orig):
        return lambda pipe, context, *a, **k: orig(pipe, narrowed(context), *a, **k)

    return _installed(t2i, sweep, phase1, phase2)


def no_edit():
    """Fault: the attention control is dropped where the image is produced
    (the edit's prompts still steer the image through the context)."""
    def t2i(orig):
        return lambda pipe, prompts, controller=None, **k: orig(pipe, prompts, None, **k)

    def sweep(orig):
        return lambda pipe, context, latents, controllers, **k: orig(
            pipe, context, latents, None, **k)

    return _installed(t2i, sweep, phase1=sweep)


def altered_answer():
    """Fault: an answer altered where it is produced: the top left sixteenth
    of the edited image (a quarter of each side) comes back inverted."""
    def spoil(images):
        n = images.shape[-2] // 4
        return images.at[..., 1, :n, :n, :].set(255 - images[..., 1, :n, :n, :])

    def first_spoiled(orig):
        def run(*a, **k):
            images, *rest = orig(*a, **k)
            return (spoil(images), *rest)
        return run

    return _installed(first_spoiled, first_spoiled, phase2=first_spoiled)


def swapped_groups():
    """Fault of a batched call: the groups' answers come back in another
    order (a request gets its batch-mate's images)."""
    def reverse(orig):
        def run(*a, **k):
            images, final = orig(*a, **k)
            return images[::-1], final[::-1]
        return run

    return _installed(sweep=reverse, phase2=reverse)


FAULTS = {"no_edit": no_edit, "altered_answer": altered_answer,
          "swapped_groups": swapped_groups}
