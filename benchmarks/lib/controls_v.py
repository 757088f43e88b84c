"""The planted fault of a v-prediction configuration's output check, beside
``controls.py`` (whose control and faults apply to it as they are): the
program samples as if its U-Net predicted eps where the configuration says v.
Never used by the benchmark's own runs: ``tools/readings_v.py`` reads it on
the chip at the cell's own size, and ``tests/benchmark`` see ``correct`` come
out false with it at a toy size."""

from __future__ import annotations

import dataclasses

from .controls import _installed


def epsilon_for_v():
    """Fault: ``text2image`` is handed the pipeline with its scheduler's
    ``prediction_type`` set to ``epsilon``, so the network's v goes into the
    DDIM update as if it were eps."""
    def t2i(orig):
        def run(pipe, *a, **k):
            sched = dataclasses.replace(pipe.config.scheduler,
                                        prediction_type="epsilon")
            config = dataclasses.replace(pipe.config, scheduler=sched)
            return orig(dataclasses.replace(pipe, config=config), *a, **k)
        return run

    return _installed(t2i)


FAULTS = {"epsilon_for_v": epsilon_for_v}
