"""Device: the temporaries XLA's buffer assignment reserves for the window's
sampling program, in GiB (``memory_analysis().temp_size_in_bytes`` of the
executable, found through the launch the program kept: ``lib/launched.py``).
It stands beside ``peak_hbm_gib``, which reads the allocator's peak and does
not see them (0.40 and 0.26 GiB over the weights where XLA counts 2.14 and
4.67: PERF.md, Open questions): what a larger configuration's fit is
reckoned from. Where more than one program's loop ran in the traced window,
the largest. Read after the window, on traced runs only.

The sampling program is the module with an operation in the loop, and the
loop is the launched program's: an instruction of a ``while``'s body or
condition in the module's compiled text (``lib/launched.py:program_loops``,
``loop_modules``), so a trace that lost the ``while``'s event still names
it."""

from benchmarks.lib import launched


def read(run):
    if not run.on_chip:
        return None
    sizes = [s for m in launched.loop_modules(run) if (s := launched.temp_bytes(m))]
    return max(sizes) / 2 ** 30 if sizes else None
