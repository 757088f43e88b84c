"""Assemble the analyzer passes into one structured report.

The report is the analyzer's single output contract — ``tools/jaxcheck.py``
prints/serializes it, ``tools/quality_gate.py``'s ``static_analysis`` check
consumes it, and ``p2p-tpu check --static`` wraps it. Shape:

.. code-block:: json

    {"version": 3,
     "ok": true,
     "ast": {"findings": [...], "summary": {"new": 0, ...}},
     "contracts": {"results": [...], "ok": true},
     "compile_key": {"fields": [...], "ok": true},
     "collectives": {"results": [...], "ok": true,
                     "table": {"serve/mesh-dp2": {"ops": {},
                               "bytes_per_step": 0, ...}}},
     "wal": {"protocol": [...], "model": {"crash_points": 3722, ...},
             "seeded": [...], "ok": true}}

``ok`` is the gate verdict over the sections that ran: no *new* AST
findings (suppressed/baselined don't count) and every contract,
compile-key and shardcheck verdict holding. ``collectives.table`` is the
per-program bytes-per-step comms budget (:mod:`.collectives`) downstream
mesh work designs against. Sections are selectable (``only=`` /
``tools/jaxcheck.py --only collectives``) for fast local iteration; the
default runs everything.
"""

from __future__ import annotations

import os
from typing import Iterable, List, Optional

from . import astlint
from .findings import apply_baseline, load_baseline, summarize

REPORT_VERSION = 3

#: Selectable report sections (the ``only=`` vocabulary). ``ast`` is pass
#: 1; ``contracts`` bundles the jaxpr contracts with the compile-key sweep
#: (they share the traced canonical set); ``collectives`` is shardcheck;
#: ``cost`` is the cost observatory's canonical pass (XLA cost cards for
#: the canonical serve programs, diffed against the frozen budgets in
#: ``tools/cost_budgets.json`` — ISSUE 14); ``wal`` is pass 5 (ISSUE 20):
#: the WAL protocol completeness sweep + the exhaustive small-scope crash
#: model checker + the seeded verdict-flips (jax-free, like ``ast``).
SECTIONS = ("ast", "contracts", "collectives", "cost", "wal")

#: Default lint targets, relative to the repo root: the package plus the
#: drivers that embed repo invariants. tests/ is deliberately out — tests
#: exercise anti-patterns on purpose (fixture snippets for these very
#: rules would self-flag).
DEFAULT_LINT_PATHS = ("p2p_tpu", "tools/quality_gate.py",
                      "tools/jaxcheck.py", "tools/loadgen.py",
                      "tools/chaos_drill.py", "tools/check_checkpoint.py",
                      "tools/parity_real_weights.py", "tools/perfscope.py",
                      "__graft_entry__.py")

DEFAULT_BASELINE = os.path.join("tools", "jaxcheck_baseline.json")


def repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def run_ast_pass(paths: Optional[Iterable[str]] = None,
                 baseline_path: Optional[str] = None,
                 root: Optional[str] = None) -> dict:
    """Pass 1 over ``paths`` (default: the package + drivers), baselined
    against ``baseline_path`` (default: the committed baseline; pass "" to
    skip baselining)."""
    root = root or repo_root()
    abs_paths = [p if os.path.isabs(p) else os.path.join(root, p)
                 for p in (paths if paths is not None else
                           DEFAULT_LINT_PATHS)]
    # A missing target is an error, never a silent skip: a typo'd CI path
    # (or a renamed default) would otherwise report clean forever.
    missing = [p for p in abs_paths if not os.path.exists(p)]
    if missing:
        raise FileNotFoundError(
            f"lint target(s) do not exist: {missing}")
    findings = astlint.lint_paths(abs_paths, repo_root=root)
    if baseline_path is None:
        baseline_path = os.path.join(root, DEFAULT_BASELINE)
    if baseline_path:
        apply_baseline(findings, load_baseline(baseline_path))
    return {"findings": findings, "summary": summarize(findings)}


def run_contract_pass(pipe=None, buckets=(1, 2, 4, 8),
                      compile_key_fields: Optional[List[str]] = None) -> dict:
    """Pass 2: jaxpr contracts + the compile-key completeness sweep. Built
    lazily so the AST-only path never imports jax."""
    from . import compile_key as ck_mod
    from . import contracts as contracts_mod

    if pipe is None:
        pipe = contracts_mod.tiny_pipeline()
    results = contracts_mod.run_contracts(pipe, buckets=buckets)
    verdicts = ck_mod.check_compile_key(pipe, fields=compile_key_fields)
    # The split per-phase pool keys sweep the same schema against a gated
    # base (verdicts land as <field>@phase1 / <field>@phase2): the
    # hand-off's cache-poisoning guard rides the same report gate.
    verdicts += ck_mod.check_phase_keys(pipe, fields=compile_key_fields)
    # The semantic cache's content_key sweeps the same schema against the
    # declared OUTPUT_DETERMINING map (ISSUE 13): a field that determines
    # the output images but not the key is cache poisoning — wrong images
    # served bitwise-confidently — so it rides the same report gate.
    content = ck_mod.check_content_key(pipe, fields=compile_key_fields)
    return {
        "contracts": {"results": results,
                      "ok": all(r.ok for r in results)},
        "compile_key": {"fields": verdicts,
                        "ok": all(v.ok for v in verdicts)},
        "content_key": {"fields": content,
                        "ok": all(v.ok for v in content)},
    }


def run_collectives_pass(pipe=None, collective_dps=None) -> dict:
    """Pass 3: shardcheck — the declared-collective / no-hidden-resharding
    / no-host-boundary contracts over the compiled mesh serve programs,
    plus the per-program bytes-per-step comms table (:mod:`.collectives`).
    Lazy-imported for the same reason as pass 2 (and because this pass
    additionally pays an XLA compile per program)."""
    from . import collectives as coll_mod

    dps = (coll_mod.SHARDCHECK_DPS if collective_dps is None
           else tuple(collective_dps))
    results, table = coll_mod.check_collectives(pipe, dps=dps)
    return {"collectives": {"results": results,
                            "ok": all(r.ok for r in results),
                            "table": table}}


def run_cost_pass(pipe=None, budgets_path: Optional[str] = None,
                  root: Optional[str] = None) -> dict:
    """Pass 4: the cost observatory's canonical pass (ISSUE 14) — compile
    the canonical serve programs, extract their XLA cost cards
    (``obs.costmodel``), and diff the budget-frozen fields against
    ``tools/cost_budgets.json``. Lazy-imported like the other traced
    passes (this one additionally pays an XLA compile per program)."""
    from ..obs import costmodel

    cards = costmodel.canonical_cost_cards(pipe)
    if budgets_path is None:
        budgets_path = os.path.join(root or repo_root(),
                                    costmodel.DEFAULT_BUDGETS)
    budget = costmodel.load_budgets(budgets_path)
    verdicts = costmodel.check_budgets(cards, budget)
    return {"cost": {"programs": cards,
                     "budget": verdicts,
                     "ok": all(v.ok for v in verdicts)}}


def run_wal_pass(root: Optional[str] = None, scope=None,
                 seeded: bool = True) -> dict:
    """Pass 5 (ISSUE 20): the WAL protocol checker — (a) the completeness
    sweep (declaration ↔ write-time registry ↔ append sites ↔ replay fold
    branches ↔ chaos crash windows), (b) the exhaustive small-scope crash
    model check through the real ``replay()`` (default
    :data:`walcheck.TIER1_SCOPE`; the pass fails on any invariant
    violation OR on incomplete kind/window coverage), and (c) the seeded
    verdict-flips — the three planted protocol bugs must each flip, so a
    checker that has gone blind fails its own report. Pure Python + the
    journal loaded by path: no jax import."""
    from . import protocol as protocol_mod
    from . import walcheck as walcheck_mod

    verdicts = protocol_mod.check_protocol(root)
    model = walcheck_mod.run_walcheck(
        scope=scope or walcheck_mod.TIER1_SCOPE, root=root)
    section = {"protocol": verdicts, "model": model,
               "ok": all(v.ok for v in verdicts) and model["ok"]}
    if seeded:
        flips = walcheck_mod.run_seeded_bugs(root)
        section["seeded"] = flips
        section["ok"] = section["ok"] and all(f["flipped"] for f in flips)
    return {"wal": section}


def run_all(paths: Optional[Iterable[str]] = None,
            baseline_path: Optional[str] = None,
            root: Optional[str] = None,
            ast_only: bool = False,
            buckets=(1, 2, 4, 8),
            only: Optional[str] = None,
            collective_dps=None,
            sections: Optional[Iterable[str]] = None) -> dict:
    """Run the selected sections (default: all). ``ast_only`` is the
    historical spelling of ``only="ast"``; ``only`` narrows to one section
    (``tools/jaxcheck.py --only``); ``sections`` picks an explicit subset
    (the quality gate's ``static_analysis`` check runs the three analyzer
    passes here and the ``cost`` pass in its own ``cost_regression`` leg,
    so the canonical programs compile once per gate run, not twice);
    ``collective_dps`` narrows the shardcheck dp sweep (the quality gate
    runs one dp for speed, the analyzer's own tests sweep the axis)."""
    if only is not None and only not in SECTIONS:
        raise ValueError(f"only must be one of {SECTIONS}, got {only!r}")
    if ast_only:
        only = "ast"
    if only is not None:
        sections = (only,)
    elif sections is None:
        sections = SECTIONS
    else:
        sections = tuple(sections)
        unknown = set(sections) - set(SECTIONS)
        if unknown:
            raise ValueError(f"sections must be from {SECTIONS}, "
                             f"got {sorted(unknown)}")
    report: dict = {"version": REPORT_VERSION}
    oks = []
    if "ast" in sections:
        ast = run_ast_pass(paths, baseline_path=baseline_path, root=root)
        report["ast"] = ast
        oks.append(ast["summary"]["new"] == 0)
    pipe = None
    if ("contracts" in sections or "collectives" in sections
            or "cost" in sections):
        # The traced passes share one tiny pipeline (same construction,
        # no reason to re-init weights per pass).
        from . import contracts as contracts_mod

        pipe = contracts_mod.tiny_pipeline()
    if "contracts" in sections:
        passes = run_contract_pass(pipe, buckets=buckets)
        report.update(passes)
        oks += [passes["contracts"]["ok"], passes["compile_key"]["ok"],
                passes["content_key"]["ok"]]
    if "collectives" in sections:
        coll = run_collectives_pass(pipe, collective_dps=collective_dps)
        report.update(coll)
        oks.append(coll["collectives"]["ok"])
    if "cost" in sections:
        cost = run_cost_pass(pipe, root=root)
        report.update(cost)
        oks.append(cost["cost"]["ok"])
    if "wal" in sections:
        wal = run_wal_pass(root=root)
        report.update(wal)
        oks.append(wal["wal"]["ok"])
    report["ok"] = all(oks)
    return report


def to_json_dict(report: dict) -> dict:
    """The report with dataclasses rendered to plain dicts (the JSON file
    quality_gate and CI artifacts consume)."""
    out = {"version": report["version"], "ok": report["ok"]}
    if "ast" in report:
        out["ast"] = {"findings": [f.to_dict()
                                   for f in report["ast"]["findings"]],
                      "summary": report["ast"]["summary"]}
    if "contracts" in report:
        out["contracts"] = {
            "ok": report["contracts"]["ok"],
            "results": [r.to_dict()
                        for r in report["contracts"]["results"]]}
    if "compile_key" in report:
        out["compile_key"] = {
            "ok": report["compile_key"]["ok"],
            "fields": [{"field": v.field,
                        "program_changed": v.program_changed,
                        "key_changed": v.key_changed,
                        "ok": v.ok, "problem": v.problem}
                       for v in report["compile_key"]["fields"]]}
    if "content_key" in report:
        out["content_key"] = {
            "ok": report["content_key"]["ok"],
            "fields": [{"field": v.field,
                        "output_determining": v.output_determining,
                        "key_changed": v.key_changed,
                        "ok": v.ok, "problem": v.problem}
                       for v in report["content_key"]["fields"]]}
    if "collectives" in report:
        out["collectives"] = {
            "ok": report["collectives"]["ok"],
            "results": [r.to_dict()
                        for r in report["collectives"]["results"]],
            "table": report["collectives"]["table"]}
    if "cost" in report:
        out["cost"] = {
            "ok": report["cost"]["ok"],
            "programs": report["cost"]["programs"],
            "budget": [v.to_dict() for v in report["cost"]["budget"]]}
    if "wal" in report:
        w = report["wal"]
        out["wal"] = {
            "ok": w["ok"],
            "protocol": [v.to_dict() for v in w["protocol"]],
            "model": w["model"]}
        if "seeded" in w:
            out["wal"]["seeded"] = w["seeded"]
    return out


def render_text(report: dict, verbose: bool = False) -> str:
    """Human-readable rendering (the CLI's default output)."""
    lines: List[str] = []
    if "ast" in report:
        s = report["ast"]["summary"]
        lines.append(f"AST pass: {s['new']} new finding(s) "
                     f"({s['suppressed']} suppressed, {s['baselined']} "
                     f"baselined, {s['total']} total)")
        for f in report["ast"]["findings"]:
            if f.is_new or verbose:
                lines.append("  " + f.format())
    if "contracts" in report:
        c = report["contracts"]
        lines.append(f"Contract pass: "
                     f"{sum(1 for r in c['results'] if not r.ok)} "
                     f"failure(s) across {len(c['results'])} check(s)")
        for r in c["results"]:
            if not r.ok or verbose:
                lines.append("  " + r.format())
    if "compile_key" in report:
        k = report["compile_key"]
        lines.append(f"Compile-key sweep: "
                     f"{sum(1 for v in k['fields'] if not v.ok)} "
                     f"violation(s) across {len(k['fields'])} field(s)")
        for v in k["fields"]:
            if not v.ok or verbose:
                lines.append("  " + v.format())
    if "content_key" in report:
        k = report["content_key"]
        lines.append(f"Content-key sweep: "
                     f"{sum(1 for v in k['fields'] if not v.ok)} "
                     f"violation(s) across {len(k['fields'])} field(s)")
        for v in k["fields"]:
            if not v.ok or verbose:
                lines.append("  " + v.format())
    if "collectives" in report:
        c = report["collectives"]
        lines.append(f"Shardcheck pass: "
                     f"{sum(1 for r in c['results'] if not r.ok)} "
                     f"failure(s) across {len(c['results'])} check(s)")
        for r in c["results"]:
            if not r.ok or verbose:
                lines.append("  " + r.format())
        lines.append("  collective budget (bytes/step | bytes once | ops):")
        for name in sorted(c["table"]):
            row = c["table"][name]
            lines.append(f"    {name:26s} {row['bytes_per_step']:>10d} | "
                         f"{row['bytes_once']:>10d} | {row['ops'] or '{}'}")
    if "cost" in report:
        c = report["cost"]
        lines.append(f"Cost pass: "
                     f"{sum(1 for v in c['budget'] if not v.ok)} budget "
                     f"violation(s) across {len(c['budget'])} check(s)")
        for v in c["budget"]:
            if not v.ok or verbose:
                lines.append("  " + v.format())
        lines.append("  cost cards (flops | bytes accessed | intensity):")
        for name in sorted(c["programs"]):
            card = c["programs"][name]
            lines.append(f"    {name:26s} {card['flops']:>14.4g} | "
                         f"{card['bytes_accessed']:>14.4g} | "
                         f"{card['arith_intensity']:>7.2f}")
    if "wal" in report:
        w = report["wal"]
        m = w["model"]
        lines.append(f"WAL protocol pass: "
                     f"{sum(1 for v in w['protocol'] if not v.ok)} sweep "
                     f"failure(s) across {len(w['protocol'])} check(s)")
        for v in w["protocol"]:
            if not v.ok or verbose:
                lines.append("  " + v.format())
        lines.append(f"  model check [{m['scope']}]: {m['traces']} "
                     f"trace(s), {m['crash_points']} crash point(s), "
                     f"{len(m['violations'])} violation(s)")
        for viol in m["violations"]:
            lines.append(f"    {viol['invariant']} at {viol['point']} "
                         f"({viol['window']}) of [{viol['trace']}]: "
                         f"{viol['detail']}")
        for missing, what in ((m["kinds_missing"], "record/event kind(s)"),
                              (m["windows_missing"], "crash window(s)")):
            if missing:
                lines.append(f"    COVERAGE: {what} never exercised: "
                             f"{missing}")
        for flip in w.get("seeded", ()):
            status = "flips" if flip["flipped"] else "DOES NOT FLIP"
            lines.append(f"  seeded bug {flip['bug']}: {status}"
                         + (f" — {flip['violation']['invariant']} at "
                            f"{flip['counterexample']}"
                            if flip["flipped"] else ""))
    lines.append("static analysis " + ("PASSED" if report["ok"]
                                       else "FAILED"))
    return "\n".join(lines)
