"""Model: device time a denoising step spends under ``self_attn/<site>/core``
of the self-attention sites the window's program ran on the flash kernel
(``kernel`` in the launch's record: untouched sites from 1,024 keys up, the
ten of the two largest levels in both cells since PR 30), in ms: the kernel
and the scaling and layout copy of ``q`` fused ahead of it. With
``model.self_attn_edited_ms_per_step`` it accounts for
``model.self_attn_ms_per_step`` less the sites' ``qkv`` and ``out``
(``lib/self_sites.py``).

The loop is the launched program's: an operation is in it where its
instruction belongs to a ``while``'s body or condition in the compiled
text of its module (``lib/launched.py:program_loops``,
``lib/trace.py:mark_loops``), by nesting under the trace's ``while`` event
only where no text exists; a trace whose loop does not add up to the
traced calls' steps is not read (``lib/trace.py:incomplete``)."""

from benchmarks.lib import self_sites


def read(run):
    return self_sites.core_ms_per_step(run, "kernel")
