"""What the program kept of the programs it launched
(``p2p_tpu/obs/launches.py``), for the readers that ask how the window's
sampling program ran and what XLA reserved for it: a trace names a program
by its module, and the registry's newest launch of that module is the
program (as for the scope index, ``lib/scopes.py``).

A program that keeps no registry (any tree before PR 27), or a launch that
lacks the field asked for, makes every function here return None: the result
line then leaves the metric out.
"""

from __future__ import annotations

import sys
import time

from . import trace as T


def program_text(module: str):
    """The compiled text of ``module``'s newest program, lowered again from
    the shapes its launch kept (a read of the executable in memory after a
    launch in the same process); None without a launch."""
    launch = newest(module)
    if launch is None:
        return None
    t0 = time.monotonic()
    text = launch.fn.lower(*launch.args, **launch.kwargs).compile().as_text()
    print(f"program text of {module}: {len(text)} characters in "
          f"{time.monotonic() - t0:.2f} s", file=sys.stderr)
    return text


def program_loops(run):
    """``{module: lib.trace.Loops or None}`` for every module of the trace:
    the loops of the program's text (``run.program_texts`` by module where a
    recorded trace brings them, else the launch's), None where there is no
    text and ``Op.loop`` stays the nesting's."""
    recorded = getattr(run, "program_texts", None)
    modules = {o.module for ops in run.trace_data.devices.values() for o in ops}
    out = {}
    for m in sorted(modules):
        text = recorded.get(m) if recorded is not None else program_text(m)
        out[m] = T.program_loops(text) if text else None
    return out


def loop_modules(run):
    """The modules whose sampling loop ran in the traced window (``Op.loop``
    from the program's text, ``lib/trace.py``)."""
    if run.trace_data is None:
        return []
    lo, hi = run.trace_window
    return sorted({o.module for o in T.leaf_ops(run.trace_data, lo, hi) if o.loop})


def newest(module: str):
    """The registry's newest launch of ``module``; None without one."""
    try:
        from p2p_tpu.obs import launches
    except ImportError:
        return None
    known = launches.programs(module)
    return known[-1] if known else None


def self_site_hows(run, module: str):
    """``{site index: "kernel" | "einsum" | "edited" | "sharded"}``: how each
    self-attention site of ``module``'s program ran, as the model noted while
    the program was traced (``Launch.self_sites``), or as a recorded trace
    brings it along (``run.self_site_hows``, by module)."""
    recorded = getattr(run, "self_site_hows", None)
    if recorded is not None:
        return recorded.get(module)
    sites = getattr(newest(module), "self_sites", None)
    return {i: s.how for i, s in sites.items()} if sites else None


def temp_bytes(module: str):
    """Bytes of temporaries XLA's buffer assignment gives ``module``'s program
    (``memory_analysis()`` of the executable): lowered again from the shapes
    the launch kept and compiled, which after a launch in the same process is
    a read of the executable in memory or of the persistent cache."""
    launch = newest(module)
    if launch is None:
        return None
    t0 = time.monotonic()
    compiled = launch.fn.lower(*launch.args, **launch.kwargs).compile()
    stats = compiled.memory_analysis()
    if stats is None:
        return None
    print(f"memory of {module} by XLA's count, bytes: "
          f"temporaries {stats.temp_size_in_bytes} arguments "
          f"{stats.argument_size_in_bytes} outputs {stats.output_size_in_bytes} "
          f"code {stats.generated_code_size_in_bytes} "
          f"(read in {time.monotonic() - t0:.2f} s)", file=sys.stderr)
    return stats.temp_size_in_bytes
