"""Entry: host time inside the program's own entry spans per traced call, in
ms: the sum of the self times of ``entry.controller``, ``entry.text2image``,
``entry.prepare``, ``entry.tokenize``, ``entry.encode`` and
``sampler.text2image`` (the dispatch), read from the program's span ring and
joined by span id. ``entry.host_ms_per_call`` is the same layer by
subtraction from outside; the difference is landing the images and the
harness."""

from benchmarks.lib import scopes


def read(run):
    return scopes.entry_self_ms_per_call(run)
