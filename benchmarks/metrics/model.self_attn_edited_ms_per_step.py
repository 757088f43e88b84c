"""Model: device time a denoising step spends under ``self_attn/<site>/core``
of the self-attention sites the window's program ran as ``edited``: the
controller injects into them, or a reader takes their maps, in ms. Below
1,024 keys, and wherever a reader takes the map, scores and softmax are
materialized with the injected map, a store's accumulation and P V; from
1,024 keys up a site the controller only injects into runs the flash kernel
on the base row's q and k in its edit rows (SDXL's sixty 32 x 32
sites). Under the paper's edit with no reader of the store these are the six
sites of the two lowest levels (16 x 16 and 8 x 8 at SD-1.4, 24 x 24 and
12 x 12 at SD-2.1); the class is read from the launch, not worked out
(``lib/self_sites.py``).

The loop is the launched program's: an operation is in it where its
instruction belongs to a ``while``'s body or condition in the compiled
text of its module (``lib/launched.py:program_loops``,
``lib/trace.py:mark_loops``), by nesting under the trace's ``while`` event
only where no text exists; a trace whose loop does not add up to the
traced calls' steps is not read (``lib/trace.py:incomplete``)."""

from benchmarks.lib import self_sites


def read(run):
    return self_sites.core_ms_per_step(run, "edited")
