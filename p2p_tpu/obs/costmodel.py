"""The cost observatory: XLA cost cards, platform peaks, roofline, MFU.

The R5 perf verdict ("40.6 ms/step ≈ 45% MFU is the XLA ceiling") was
hand-computed arithmetic in PERF.md plus a one-off ``cost_analysis()``
call in a profiling scratch script — no serve program, bench round or CI
leg could state its own FLOPs, bytes or MFU. This module makes that
arithmetic a first-class, testable data path:

- **Cost cards** (:class:`CostCard`): the XLA ``cost_analysis()`` scalars
  (flops, bytes accessed, transcendentals — behind the dict-vs-list
  API-drift guard :func:`cost_analysis_dict`, the one shared parser every
  driver now uses) plus the ``memory_analysis()`` byte budget (argument /
  output / temp / generated-code), extracted from any compiled program at
  build time.
- **Peaks** (:class:`Peaks`): per-platform peak FLOP/s + memory bytes/s.
  Known accelerators come from the datasheet table
  (:data:`PLATFORM_PEAKS` — v5e is the chip every PERF.md number was
  measured on); a CPU rehearsal host gets *calibrated microbenchmark*
  peaks (:func:`calibrated_cpu_peaks`) so the MFU/roofline arithmetic is
  exercised end to end everywhere, not only on chip.
- **Roofline + MFU** (:func:`roofline`, :func:`mfu_pct`): arithmetic
  intensity vs the ridge point classifies a program compute- vs
  bandwidth-bound and predicts its ms; measured MFU is
  ``flops ÷ measured_seconds ÷ peak`` — the exact PERF.md headline
  formula, now tool-derived (``tools/perfscope.py --headline`` reproduces
  89 TF/s ≈ 45% MFU at 40.75 ms/step from the recorded artifacts alone).
- **Frozen budgets** (:func:`load_budgets` / :func:`check_budgets`): the
  canonical programs' flops/bytes are committed in
  ``tools/cost_budgets.json`` and diffed by the default-on
  ``cost_regression`` quality-gate leg — a refactor that silently doubles
  the phase-2 program's bytes accessed fails CI *by program name*, the
  same discipline jaxcheck applies to compile keys and collectives.
- **CostScope**: the serve engine's hook. Every ``ProgramCache`` miss
  records its program's cost card (``serve --cost`` / ``--programs-out``);
  every dispatch contributes a measured-MFU observation; the serve
  summary gains a ``cost`` block and flight ``run`` segments gain
  predicted-vs-measured attribution. ``costscope=None`` (the default)
  changes nothing — not a record byte, a journal line, a compiled
  program or a metric family (the same disabled-mode discipline as
  flight/slo/semcache).
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Dict, List, Optional

from . import metrics as metrics_mod

#: Default location of the frozen per-canonical-program budgets, relative
#: to the repo root (tools/perfscope.py --update-budgets rewrites it).
DEFAULT_BUDGETS = os.path.join("tools", "cost_budgets.json")

#: Budget-frozen cost-card fields: program *shape* facts (deterministic
#: for a given HLO), never timings.
BUDGET_FIELDS = ("flops", "bytes_accessed")

#: Relative drift tolerance for the budget diff: generous enough that
#: XLA-version jitter and fusion-order noise never flap the gate, tight
#: enough that a structural regression (a 2x bytes blow-up, a vanished
#: cache) cannot hide.
DEFAULT_RTOL = 0.25

#: MFU percentage histogram bounds (CostScope's dispatch observations).
MFU_PCT_BUCKETS = (1.0, 2.0, 5.0, 10.0, 15.0, 20.0, 30.0, 40.0, 50.0,
                   60.0, 70.0, 80.0, 90.0, 100.0)


# ---------------------------------------------------------------------------
# cost_analysis / memory_analysis extraction
# ---------------------------------------------------------------------------


def cost_analysis_dict(compiled) -> dict:
    """The ``cost_analysis()`` properties of a compiled program as one flat
    dict.

    ``cost_analysis()`` returns a plain dict, or None where the backend
    exposes nothing ({} then)."""
    return dict(compiled.cost_analysis() or {})


def memory_analysis_dict(compiled) -> dict:
    """The scalar byte counters of ``memory_analysis()`` as a plain dict
    ({} when the backend exposes nothing). Only the stable numeric
    attributes are read — the stats object also carries a serialized HLO
    proto that must never leak into a JSON artifact."""
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return {}
    if ma is None:
        return {}
    out = {}
    for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "alias_size_in_bytes",
                 "generated_code_size_in_bytes"):
        val = getattr(ma, attr, None)
        if isinstance(val, (int, float)):
            out[attr] = int(val)
    return out


@dataclasses.dataclass
class CostCard:
    """One program's build-time cost facts (see the module docstring)."""

    program: str
    flops: float = 0.0
    bytes_accessed: float = 0.0
    transcendentals: float = 0.0
    argument_bytes: int = 0
    output_bytes: int = 0
    temp_bytes: int = 0
    generated_code_bytes: int = 0
    build_ms: float = 0.0          # lowering + XLA compile wall time
    warm_ms: float = 0.0           # warm-up execution wall time

    @property
    def peak_bytes(self) -> int:
        """The resident-byte budget the executable needs at once
        (arguments + outputs + temporaries + code)."""
        return (self.argument_bytes + self.output_bytes + self.temp_bytes
                + self.generated_code_bytes)

    @property
    def arith_intensity(self) -> float:
        """FLOPs per byte accessed (0 when bytes are unknown)."""
        return (self.flops / self.bytes_accessed
                if self.bytes_accessed else 0.0)

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["peak_bytes"] = self.peak_bytes
        d["arith_intensity"] = self.arith_intensity
        return d


def card_from_compiled(compiled, program: str, build_ms: float = 0.0,
                       warm_ms: float = 0.0) -> CostCard:
    """Extract a :class:`CostCard` from a ``jax.stages.Compiled``."""
    ca = cost_analysis_dict(compiled)
    ma = memory_analysis_dict(compiled)
    return CostCard(
        program=program,
        flops=float(ca.get("flops", 0.0)),
        bytes_accessed=float(ca.get("bytes accessed", 0.0)),
        transcendentals=float(ca.get("transcendentals", 0.0)),
        argument_bytes=ma.get("argument_size_in_bytes", 0),
        output_bytes=ma.get("output_size_in_bytes", 0),
        temp_bytes=ma.get("temp_size_in_bytes", 0),
        generated_code_bytes=ma.get("generated_code_size_in_bytes", 0),
        build_ms=float(build_ms), warm_ms=float(warm_ms))


# ---------------------------------------------------------------------------
# Platform peaks
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Peaks:
    """Peak FLOP/s and memory bytes/s of one device, with provenance."""

    flops_per_s: float
    bytes_per_s: float
    platform: str = "unknown"
    source: str = "fake"          # "datasheet" | "calibrated" | "fake"

    @property
    def ridge(self) -> float:
        """Arithmetic intensity (flops/byte) at the roofline ridge point:
        programs above it are compute-bound, below it bandwidth-bound."""
        return (self.flops_per_s / self.bytes_per_s
                if self.bytes_per_s else 0.0)

    def to_dict(self) -> dict:
        return {**dataclasses.asdict(self), "ridge": self.ridge}


#: Datasheet peaks by ``device_kind`` substring (lower-cased match). The
#: v5e row is the chip every PERF.md number was measured on (bf16 matmul
#: ≈ 197 TF/s, HBM ≈ 819 GB/s — PERF.md "Hardware & workload").
PLATFORM_PEAKS = {
    "v5 lite": Peaks(197e12, 819e9, "tpu v5e", "datasheet"),
    "v5e": Peaks(197e12, 819e9, "tpu v5e", "datasheet"),
    "v5p": Peaks(459e12, 2765e9, "tpu v5p", "datasheet"),
    "v4": Peaks(275e12, 1228e9, "tpu v4", "datasheet"),
}

_CPU_PEAKS_CACHE: List[Optional[Peaks]] = [None]


def calibrated_cpu_peaks(refresh: bool = False) -> Peaks:
    """Microbenchmark-calibrated peaks for the rehearsal host, cached per
    process: a jitted f32 matmul for FLOP/s, a jitted add-copy for
    bytes/s (best-of-3 each, so a scheduler hiccup cannot deflate the
    peak and inflate every MFU computed against it). CPU MFU numbers are
    *relative to this calibration*, which is exactly what makes the
    roofline arithmetic testable off-chip — they are not comparable to
    datasheet-peak MFU on an accelerator and are labeled
    ``source="calibrated"`` so no artifact can confuse the two."""
    if _CPU_PEAKS_CACHE[0] is not None and not refresh:
        return _CPU_PEAKS_CACHE[0]
    import jax
    import jax.numpy as jnp
    import numpy as np

    n = 512
    a = jnp.asarray(np.random.RandomState(0).rand(n, n), jnp.float32)
    mm = jax.jit(lambda x, y: x @ y)
    jax.block_until_ready(mm(a, a))              # compile
    t_mm = min(_timed(lambda: jax.block_until_ready(mm(a, a)))
               for _ in range(3))
    flops_per_s = 2.0 * n ** 3 / max(t_mm, 1e-9)

    big = jnp.zeros((8 * 1024 * 1024,), jnp.float32)      # 32 MiB
    add = jax.jit(lambda x: x + 1.0)
    jax.block_until_ready(add(big))              # compile
    t_add = min(_timed(lambda: jax.block_until_ready(add(big)))
                for _ in range(3))
    bytes_per_s = 2.0 * big.size * 4 / max(t_add, 1e-9)   # read + write

    peaks = Peaks(flops_per_s, bytes_per_s, "cpu", "calibrated")
    _CPU_PEAKS_CACHE[0] = peaks
    return peaks


def _timed(fn) -> float:
    import time

    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def detect_peaks(device=None) -> Peaks:
    """Peaks for ``device`` (default: the first local device): datasheet
    numbers for the accelerators in :data:`PLATFORM_PEAKS`, calibrated
    microbenchmarks for the CPU rehearsal host. An accelerator missing
    from the table is an error naming its ``device_kind`` — a roofline
    against made-up peaks under the device's own label would read as a
    measurement."""
    import jax

    if device is None:
        device = jax.local_devices()[0]
    if device.platform == "cpu":
        return calibrated_cpu_peaks()
    peaks = lookup_peaks(device.device_kind)
    if peaks is None:
        raise ValueError(
            f"no datasheet peaks for device_kind {device.device_kind!r} "
            f"(platform {device.platform!r}); add a PLATFORM_PEAKS row "
            f"(known: {sorted(PLATFORM_PEAKS)})")
    return peaks


def lookup_peaks(device_kind: str) -> Optional[Peaks]:
    """Datasheet peaks by device-kind substring, or None when unknown."""
    kind = (device_kind or "").lower()
    for key, peaks in PLATFORM_PEAKS.items():
        if key in kind:
            return peaks
    return None


# ---------------------------------------------------------------------------
# Roofline / MFU arithmetic
# ---------------------------------------------------------------------------


def roofline(flops: float, bytes_accessed: float, peaks: Peaks,
             devices: int = 1) -> dict:
    """Roofline verdict for one program on ``devices`` copies of
    ``peaks``: which resource bounds it, and the model-predicted ms."""
    pf = peaks.flops_per_s * max(1, devices)
    pb = peaks.bytes_per_s * max(1, devices)
    compute_s = flops / pf if pf else 0.0
    memory_s = bytes_accessed / pb if pb else 0.0
    bound = "compute" if compute_s >= memory_s else "bandwidth"
    intensity = flops / bytes_accessed if bytes_accessed else 0.0
    return {"arith_intensity": intensity,
            "ridge": peaks.ridge,
            "bound": bound,
            "compute_ms": compute_s * 1e3,
            "memory_ms": memory_s * 1e3,
            "predicted_ms": max(compute_s, memory_s) * 1e3}


def mfu_pct(flops: float, run_ms: float, peaks: Peaks,
            devices: int = 1) -> Optional[float]:
    """Measured model-FLOPs utilization: ``flops / seconds / peak`` as a
    percentage — the PERF.md headline formula. None when the run time is
    unusable (a zero-timer rehearsal run measures control flow, not
    compute)."""
    if run_ms <= 0.0 or flops <= 0.0 or peaks.flops_per_s <= 0.0:
        return None
    return (flops / (run_ms / 1e3)
            / (peaks.flops_per_s * max(1, devices))) * 100.0


# ---------------------------------------------------------------------------
# Frozen budgets
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class BudgetVerdict:
    """One (program, field) budget comparison."""

    program: str
    field: str
    frozen: Optional[float]
    measured: Optional[float]
    ok: bool
    problem: str = ""

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    def format(self) -> str:
        ratio = ("-" if not (self.frozen and self.measured)
                 else f"{self.measured / self.frozen:.3f}x")
        return (f"{'ok  ' if self.ok else 'FAIL'} cost_budget "
                f"{self.program:18s} {self.field:14s} {ratio:>8s} "
                f"{self.problem}")


def load_budgets(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def check_budgets(cards: Dict[str, dict], budgets: dict,
                  rtol: Optional[float] = None) -> List[BudgetVerdict]:
    """Diff measured canonical cost cards against the frozen budgets.

    Failures name the program (the acceptance contract: a perturbed
    phase-2 bytes budget must fail ``cost_regression`` *by name*). Both
    directions are covered: a frozen program with no card means the
    canonical set silently lost a program; a card with no frozen entry
    means a new canonical program shipped without freezing its budget."""
    if rtol is None:
        rtol = float(budgets.get("rtol", DEFAULT_RTOL))
    frozen_programs = budgets.get("programs", {})
    out: List[BudgetVerdict] = []
    for name in sorted(frozen_programs):
        frozen = frozen_programs[name]
        card = cards.get(name)
        if card is None:
            out.append(BudgetVerdict(
                name, "presence", None, None, False,
                "canonical program missing from the cost pass"))
            continue
        for field in BUDGET_FIELDS:
            want = frozen.get(field)
            got = float(card.get(field, 0.0))
            if want is None:
                continue
            if want <= 0:
                ok = got <= 0
                problem = "" if ok else "frozen 0 but program now costs"
            else:
                ratio = got / want
                ok = abs(ratio - 1.0) <= rtol
                problem = ("" if ok else
                           f"drifted {ratio:.2f}x past the ±{rtol:.0%} "
                           f"budget (frozen {want:.4g}, measured "
                           f"{got:.4g})")
            out.append(BudgetVerdict(name, field, want, got, ok, problem))
    for name in sorted(set(cards) - set(frozen_programs)):
        out.append(BudgetVerdict(
            name, "presence", None,
            float(cards[name].get("flops", 0.0)), False,
            "program has no frozen budget (freeze it: "
            "python tools/perfscope.py --update-budgets)"))
    return out


# ---------------------------------------------------------------------------
# Canonical cost pass (the jaxcheck `cost` section / budget source)
# ---------------------------------------------------------------------------


def canonical_cost_cards(pipe=None, bucket: int = 1) -> Dict[str, dict]:
    """Cost cards for the canonical serve programs at one lane bucket:
    the monolithic sweep and the two phase-pool programs (the same
    canonical set the jaxpr contracts trace, compiled here because cost
    analysis needs the optimized executable, not the jaxpr). Input
    construction mirrors ``analysis.contracts`` exactly — the cards must
    describe the programs the contracts certify."""
    import warnings

    import jax
    import jax.numpy as jnp

    from ..analysis import contracts as contracts_mod
    from ..engine.sampler import encode_prompts, phase2_controller
    from ..parallel.sweep import sweep, sweep_phase1, sweep_phase2

    if pipe is None:
        pipe = contracts_mod.tiny_pipeline()
    steps, gate = contracts_mod.STEPS, contracts_mod.GATE
    ctrl = contracts_mod._edit_controller(pipe)
    ctx, lats, _ = contracts_mod._scan_inputs(pipe)

    def lead(x):
        return jnp.broadcast_to(x[None], (bucket,) + x.shape)

    ctx_g, lat_g = lead(ctx), lead(lats)
    ctrl_g = jax.tree_util.tree_map(lead, ctrl)

    cards: Dict[str, dict] = {}

    def compiled_card(name, lowered):
        card = card_from_compiled(lowered.compile(), name)
        cards[name] = card.to_dict()

    # The canonical gate=2-of-3 deliberately truncates the controller's
    # 0.8T edit window (same constants as the contract traces) — the
    # engine's surfaced-truncation warning is expected here, not news.
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", UserWarning)
        compiled_card(
            f"sweep/b{bucket}",
            sweep(pipe, ctx_g, lat_g, ctrl_g, num_steps=steps,
                  lower_only=True))
        compiled_card(
            f"sweep/phase1/b{bucket}",
            sweep_phase1(pipe, ctx_g, lat_g, ctrl_g, num_steps=steps,
                         gate=gate, lower_only=True))
        cond = encode_prompts(pipe, list(contracts_mod.PROMPTS))
        carry = contracts_mod._zero_carry(pipe, ctrl)
        p2 = phase2_controller(ctrl)
        ctx2 = lead(cond)
        carry_g = jax.tree_util.tree_map(lead, carry)
        p2_g = (None if p2 is None
                else jax.tree_util.tree_map(lead, p2))
        compiled_card(
            f"sweep/phase2/b{bucket}",
            sweep_phase2(pipe, ctx2, carry_g, p2_g, num_steps=steps,
                         gate=gate, lower_only=True))
        # Kernel-bearing twin (ISSUE 16): the monolithic sweep dispatched
        # through the fused-edit kernel config, under the full-coverage
        # store=False kernel controller the contracts trace. Compiled via
        # the pallas interpreter (the CPU-compilable rehearsal of the same
        # program structure), so its frozen budget pins the fused program's
        # logical footprint next to its materialized sibling's.
        from ..kernels import KernelConfig

        kctrl = contracts_mod._kernel_controller(pipe)
        kctrl_g = jax.tree_util.tree_map(lead, kctrl)
        compiled_card(
            f"sweep/kernel/b{bucket}",
            sweep(pipe, ctx_g, lat_g, kctrl_g, num_steps=steps,
                  lower_only=True, kernels=KernelConfig(interpret=True)))
    return cards


# ---------------------------------------------------------------------------
# CostScope: the serve engine's observatory hook
# ---------------------------------------------------------------------------


def _program_label(key, bucket: int) -> str:
    """Compact human label for a program-cache key: the compile key's
    parts joined, suffixed with the lane bucket. Long parts (controller
    treedef reprs) collapse to a stable short hash so the label stays
    readable while distinct programs stay distinct."""
    import hashlib

    def short(p) -> str:
        s = str(p)
        if len(s) <= 24:
            return s
        return s[:10] + "~" + hashlib.sha1(s.encode()).hexdigest()[:8]

    if isinstance(key, tuple):
        parts = "/".join(short(p) for p in key)
    else:
        parts = short(key)
    return f"{parts}@b{bucket}"


class CostScope:
    """Per-serve-run cost observatory (see the module docstring).

    One scope covers one ``serve_forever`` run: the engine records a cost
    card at every ``ProgramCache`` miss (:meth:`record_program`) and an
    observation at every dispatch (:meth:`dispatch`). The scope owns the
    peak table, the per-program aggregation, the ``--programs-out``
    artifact and the summary's ``cost`` block. Everything is host-side:
    enabling a scope never changes a compiled program, a per-request
    record or a journal byte (the per-request JSONL stream stays
    byte-identical; only the *summary* gains a ``cost`` block)."""

    def __init__(self, peaks: Optional[Peaks] = None,
                 registry: Optional[metrics_mod.Registry] = None,
                 devices: int = 1):
        self.peaks = peaks if peaks is not None else detect_peaks()
        self.devices = max(1, int(devices))
        self._programs: Dict = {}          # (key, bucket) -> program dict
        reg = registry or metrics_mod.registry()
        # Families register only when a scope exists: a cost-less serve
        # run's registry snapshot stays byte-identical to the pre-cost
        # engine's (the disabled-mode discipline).
        self._m_cards = reg.counter(
            "cost_cards_total", "program cost cards recorded at build")
        self._m_flops = reg.gauge(
            "cost_program_flops", "XLA cost_analysis flops per program",
            labels=("program",))
        self._m_bytes = reg.gauge(
            "cost_program_bytes_accessed",
            "XLA cost_analysis bytes accessed per program",
            labels=("program",))
        self._m_mfu = reg.histogram(
            "cost_dispatch_mfu_pct",
            "measured model-FLOPs utilization per dispatch",
            labels=("program",), buckets=MFU_PCT_BUCKETS)

    # -- build-time ------------------------------------------------------

    def record_program(self, key, bucket: int, compiled,
                       build_ms: float = 0.0,
                       warm_ms: float = 0.0) -> Optional[dict]:
        """Record one program's cost card at build time (a cache miss).
        Returns the program entry, or None when the executable exposes
        no cost analysis."""
        label = _program_label(key, bucket)
        card = card_from_compiled(compiled, label, build_ms=build_ms,
                                  warm_ms=warm_ms)
        if card.flops <= 0 and card.bytes_accessed <= 0:
            # Backend exposes no cost analysis: no card beats a zero-cost
            # card (a flops=0 entry would ride flight segments and the
            # summary as a confidently-measured free program).
            return None
        roof = roofline(card.flops, card.bytes_accessed, self.peaks,
                        devices=self.devices)
        entry = {**card.to_dict(), **roof,
                 "bucket": bucket,
                 "devices": self.devices,
                 "dispatches": 0, "run_ms_sum": 0.0,
                 "mfu_pct_sum": 0.0, "mfu_samples": 0}
        self._programs[(key, bucket)] = entry
        self._m_cards.inc()
        self._m_flops.labels(program=label).set(card.flops)
        self._m_bytes.labels(program=label).set(card.bytes_accessed)
        return entry

    # -- dispatch-time ---------------------------------------------------

    def dispatch(self, key, bucket: int, run_ms: float,
                 lanes: int = 0) -> dict:
        """One dispatch observation against the program's card. Returns
        the flight-segment attribution attrs ({} when the program has no
        card — e.g. a fake-runner test harness, or a zero-timer run where
        measured MFU is meaningless)."""
        entry = self._programs.get((key, bucket))
        if entry is None:
            return {}
        entry["dispatches"] += 1
        entry["run_ms_sum"] += float(run_ms)
        attrs = {"predicted_ms": round(entry["predicted_ms"], 3)}
        mfu = mfu_pct(entry["flops"], run_ms, self.peaks,
                      devices=self.devices)
        if mfu is not None:
            entry["mfu_pct_sum"] += mfu
            entry["mfu_samples"] += 1
            self._m_mfu.labels(program=entry["program"]).observe(mfu)
            attrs["mfu_pct"] = round(mfu, 2)
        return attrs

    # -- artifacts -------------------------------------------------------

    def programs(self) -> List[dict]:
        """Per-program entries in build order, with derived means."""
        out = []
        for entry in self._programs.values():
            d = dict(entry)
            n = d.pop("dispatches")
            run_sum = d.pop("run_ms_sum")
            mfu_sum = d.pop("mfu_pct_sum")
            mfu_n = d.pop("mfu_samples")
            d["dispatches"] = n
            d["mean_run_ms"] = (run_sum / n) if n else 0.0
            d["mean_mfu_pct"] = (mfu_sum / mfu_n) if mfu_n else None
            out.append(d)
        return out

    def write_programs_jsonl(self, fp) -> int:
        """One JSON line per recorded program (``serve --programs-out``);
        returns lines written."""
        n = 0
        for entry in self.programs():
            fp.write(json.dumps(entry) + "\n")
            n += 1
        return n

    def summary(self) -> dict:
        """The serve summary's ``cost`` block."""
        progs = self.programs()
        dispatched = [p for p in progs if p["dispatches"]]
        mfus = [p["mean_mfu_pct"] for p in dispatched
                if p["mean_mfu_pct"] is not None]
        return {
            "peaks": self.peaks.to_dict(),
            "devices": self.devices,
            "n_programs": len(progs),
            "n_dispatches": sum(p["dispatches"] for p in progs),
            "mean_mfu_pct": (sum(mfus) / len(mfus)) if mfus else None,
            "programs": [
                {k: p[k] for k in
                 ("program", "bucket", "flops", "bytes_accessed",
                  "arith_intensity", "bound", "predicted_ms", "build_ms",
                  "warm_ms", "dispatches", "mean_run_ms", "mean_mfu_pct")}
                for p in progs],
        }
