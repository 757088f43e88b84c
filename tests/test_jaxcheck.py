"""The analyzer's own tests (ISSUE 5): per-rule fire/no-fire fixtures,
suppression + baseline semantics, the mechanical fixer, seeded violations
of every contract class, and the compile-key completeness sweep — including
the acceptance regression that masks a jaxpr-affecting field from
``compile_key`` and asserts the sweep catches the seeded omission.

The AST-pass tests are pure Python (no jax, milliseconds). The contract
tests trace real TINY programs on the session pipeline (`tiny_pipe`) —
tracing only, no XLA compile.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from p2p_tpu.analysis import astlint, fixes
from p2p_tpu.analysis import findings as findings_mod
from p2p_tpu.analysis import report as report_mod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def lint(src, rules=None, path="mod.py"):
    return [f for f in astlint.lint_source(textwrap.dedent(src), path,
                                           rules=rules)
            if f.is_new]


# ---------------------------------------------------------------------------
# Pass 1 — one fire + one no-fire fixture per rule
# ---------------------------------------------------------------------------


def test_traced_branch_fires_in_scan_body():
    hits = lint("""
        from jax import lax

        def body(carry, x):
            if x > 0:
                carry = carry + x
            return carry, x

        def run(xs):
            return lax.scan(body, 0.0, xs)
        """, rules=("traced-branch",))
    assert [f.rule for f in hits] == ["traced-branch"]
    assert "tracing freezes one side" in hits[0].message


def test_traced_branch_static_idioms_dont_fire():
    # Shape facts, None checks, bare flags, and untraced functions are the
    # legitimate static branches jit code lives on.
    assert lint("""
        from jax import lax

        def body(carry, x):
            if x.shape[0] > 1:
                carry = carry * 2
            if carry is None:
                carry = x
            return carry, x

        def run(xs, flag):
            if xs > 0:   # not a traced function: plain Python is fine
                pass
            return lax.scan(body, 0.0, xs)
        """, rules=("traced-branch",)) == []


def test_traced_branch_through_partial_and_decorator():
    hits = lint("""
        from functools import partial
        import jax

        @partial(jax.jit, static_argnames=("n",))
        def f(x, n):
            while x < n:
                x = x * 2
            return x
        """, rules=("traced-branch",))
    assert len(hits) == 1 and "`while`" in hits[0].message


def test_host_sync_fires_on_item_and_float():
    hits = lint("""
        import jax

        @jax.jit
        def f(x):
            y = x * 2
            a = y.item()
            b = float(x)
            return a + b
        """, rules=("host-sync",))
    assert len(hits) == 2
    assert any(".item()" in f.message for f in hits)
    assert any("float()" in f.message for f in hits)


def test_host_sync_static_attrs_and_untraced_dont_fire():
    assert lint("""
        import jax
        import numpy as np

        @jax.jit
        def f(x):
            n = len(x)          # static fact
            s = x.shape[0]      # static fact
            return x * n * s

        def host(x):
            return float(np.asarray(x).mean())   # not traced
        """, rules=("host-sync",)) == []


def test_impure_jit_fires_on_time_and_np_random():
    hits = lint("""
        import time
        import jax
        import numpy as np

        @jax.jit
        def f(x):
            t = time.time()
            r = np.random.rand()
            return x + t + r
        """, rules=("impure-jit",))
    assert len(hits) == 2
    assert all("baked into the program" in f.message for f in hits)


def test_impure_jit_exempts_rng_handle_and_host_code():
    assert lint("""
        import time
        import jax
        import numpy as np

        @jax.jit
        def f(x, key):
            return x + jax.random.normal(key, x.shape)

        def host():
            rng = np.random.default_rng(0)   # exempt handle
            return time.time()               # untraced
        """, rules=("impure-jit",)) == []


def test_f64_literal_fires_on_jnp_dtype_and_astype():
    hits = lint("""
        import jax.numpy as jnp

        def f(x):
            a = jnp.zeros(3, dtype=jnp.float64)
            b = x.astype("float64")
            return a, b
        """, rules=("f64-literal",))
    assert len(hits) == 2


def test_f64_literal_numpy_hostside_is_fine():
    # Host-side f64 accumulation with numpy is the *recommended* pattern.
    assert lint("""
        import numpy as np

        def accumulate(xs):
            return np.zeros(3, dtype=np.float64) + np.asarray(xs, np.float64)
        """, rules=("f64-literal",)) == []


def test_mutable_default_fires_on_arg_and_dataclass_field():
    hits = lint("""
        import dataclasses

        def f(x, acc=[]):
            acc.append(x)
            return acc

        @dataclasses.dataclass
        class Cfg:
            sizes: list = [1, 2]
        """, rules=("mutable-default",))
    assert len(hits) == 2
    assert any("shared across every call" in f.message for f in hits)
    assert any("default_factory" in f.message for f in hits)


def test_mutable_default_factory_and_none_dont_fire():
    assert lint("""
        import dataclasses

        def f(x, acc=None, name="ok", n=3):
            return acc

        @dataclasses.dataclass
        class Cfg:
            sizes: list = dataclasses.field(default_factory=list)
        """, rules=("mutable-default",)) == []


def test_import_time_jax_fires_at_module_scope_only():
    hits = lint("""
        import jax
        import jax.numpy as jnp

        TABLE = jnp.arange(10)           # fires: import-time backend init

        def lazy():
            return jnp.arange(10)        # call time: fine

        thunk = lambda: jax.random.PRNGKey(0)   # deferred: fine
        """, rules=("import-time-jax",))
    assert len(hits) == 1 and hits[0].line == 5


def test_unused_import_fire_nofire_and_exemptions():
    hits = lint("""
        import os
        import sys

        print(sys.argv)
        """, rules=("unused-import",))
    assert len(hits) == 1 and "`os`" in hits[0].message
    # __init__.py is the re-export surface; `as`-reexports and noqa exempt.
    assert lint("import os\n", rules=("unused-import",),
                path="pkg/__init__.py") == []
    assert lint("""
        import os as os
        import sys  # noqa: F401
        """, rules=("unused-import",)) == []


def test_shadowed_name_rebind_and_param_fire_mutation_doesnt():
    hits = lint("""
        import os
        import json

        os = None                 # rebinds the import

        def f(json):              # param shadows the import
            return json

        os_environ = 1            # different name: fine
        """, rules=("shadowed-name",))
    assert len(hits) == 2
    assert lint("""
        import os

        os.environ["K"] = "v"     # mutation through the import, not rebind
        """, rules=("shadowed-name",)) == []


def test_parse_error_is_a_finding_not_a_crash():
    hits = astlint.lint_source("def f(:\n", "bad.py")
    assert [f.rule for f in hits] == ["parse-error"]


_TRANSFER_SRC = """
    import numpy as np
    import jax.numpy as jnp

    def dispatch(x, seed):
        a = np.asarray(x)            # implicit d2h sync
        b = jnp.asarray(seed)        # implicit h2d transfer
        return a, b
    """


def test_unguarded_transfer_fires_only_in_dispatch_modules():
    hits = lint(_TRANSFER_SRC, rules=("unguarded-transfer",),
                path="p2p_tpu/serve/programs.py")
    assert len(hits) == 2
    assert any("d2h" in f.message for f in hits)
    assert any("h2d" in f.message for f in hits)
    # The same code outside the dispatch path is host-side prep: no fire.
    assert lint(_TRANSFER_SRC, rules=("unguarded-transfer",),
                path="p2p_tpu/utils/images.py") == []


def test_unguarded_transfer_sanctioned_idioms_dont_fire():
    # The explicit spellings the dispatch path is BUILT on: d2h lands via
    # jax.device_get (host-copying the result is fine), h2d stages through
    # stage_host / jax.device_put (wrapping a host constructor directly).
    assert lint("""
        import numpy as np
        import jax

        from ..engine.sampler import stage_host

        def dispatch(x, req):
            host = np.asarray(jax.device_get(x))
            seed = stage_host(np.int32(req.seed))
            ids = stage_host(np.asarray(req.tokens))
            dev = jax.device_put(np.asarray(req.scale))
            return host, seed, ids, dev
        """, rules=("unguarded-transfer",),
        path="p2p_tpu/serve/handoff.py") == []


def test_unguarded_transfer_dispatch_modules_are_lint_clean():
    # The committed dispatch path itself must hold the contract the rule
    # encodes (the lint-time twin of the mesh transfer-guard test).
    from p2p_tpu.analysis.astlint import DISPATCH_PATH_MODULES

    for rel in DISPATCH_PATH_MODULES:
        hits = [f for f in astlint.lint_file(
                    os.path.join(REPO, rel), repo_root=REPO,
                    rules=("unguarded-transfer",)) if f.is_new]
        assert hits == [], [f.format() for f in hits]


# ---------------------------------------------------------------------------
# Suppression + baseline semantics
# ---------------------------------------------------------------------------


def test_inline_suppression_same_line_and_above_line():
    src = textwrap.dedent("""
        import os
        # jaxcheck: disable=unused-import
        import sys
        import json  # jaxcheck: disable=unused-import
        """)
    out = astlint.lint_source(src, "mod.py", rules=("unused-import",))
    by_name = {f.message.split("`")[1]: f for f in out}
    assert not by_name["os"].suppressed       # no comment near it
    assert by_name["sys"].suppressed          # line above
    assert by_name["json"].suppressed         # trailing
    assert [f for f in out if f.is_new] == [by_name["os"]]


def test_suppression_rule_list_must_match():
    src = "import os  # jaxcheck: disable=host-sync,f64-literal\n"
    out = astlint.lint_source(src, "mod.py", rules=("unused-import",))
    assert len(out) == 1 and not out[0].suppressed


def test_suppression_with_trailing_reason_still_suppresses():
    # THE documented workflow: the disable carries its reason inline. The
    # reason text must not swallow into the rule list.
    src = ("import os  # jaxcheck: disable=unused-import -- kept: "
           "re-export for plugins\n")
    out = astlint.lint_source(src, "mod.py", rules=("unused-import",))
    assert len(out) == 1 and out[0].suppressed


def test_suppression_above_line_must_be_a_comment():
    # A code line that merely *contains* the marker in a string must not
    # suppress the line below it.
    src = 'x = "# jaxcheck: disable=unused-import"\nimport os\n'
    out = astlint.lint_source(src, "mod.py", rules=("unused-import",))
    assert len(out) == 1 and not out[0].suppressed


def test_suppression_marker_inside_string_is_content_not_directive():
    # Same-line form: directive-looking text in a string literal on the
    # flagged line itself must not suppress (tokenize, not regex-anywhere).
    src = 'import os; x = "# jaxcheck: disable=unused-import"\n'
    out = astlint.lint_source(src, "mod.py", rules=("unused-import",))
    assert len(out) == 1 and not out[0].suppressed


def test_baseline_roundtrip_is_line_number_free(tmp_path):
    src_v1 = "import os\n"
    src_v2 = "# a new comment pushes the import down\n\nimport os\n"
    f1 = astlint.lint_source(src_v1, "mod.py")
    path = str(tmp_path / "baseline.json")
    findings_mod.save_baseline(path, f1)
    doc = json.load(open(path))
    assert doc["version"] == 1 and len(doc["findings"]) == 1
    f2 = astlint.lint_source(src_v2, "mod.py")
    findings_mod.apply_baseline(f2, findings_mod.load_baseline(path))
    assert f2[0].baselined and not f2[0].is_new   # moved line, still known


def test_baseline_match_is_a_multiset():
    # Two identical offending lines, ONE baseline entry: exactly one stays
    # baselined, the other surfaces as new — deleting one of two baselined
    # duplicates must not resurrect the survivor.
    src = "import os\nimport os\n"
    fs = [f for f in astlint.lint_source(src, "m.py",
                                         rules=("unused-import",))
          if f.rule == "unused-import"]
    assert len(fs) == 1 or len(fs) == 2
    # The ctx.imports table is name-keyed, so duplicate imports collapse to
    # one finding; fabricate the duplicate-fingerprint case directly.
    if len(fs) == 1:
        fs = [fs[0], findings_mod.Finding(**{**fs[0].to_dict()})]
    baseline = [{"rule": "unused-import", "path": "m.py",
                 "code": "import os"}]
    findings_mod.apply_baseline(fs, baseline)
    assert sorted(f.baselined for f in fs) == [False, True]


def test_save_baseline_excludes_suppressed(tmp_path):
    # An inline disable is already a durable exemption; baselining it too
    # would hide a later removal of the comment.
    src = "import os  # jaxcheck: disable=unused-import\nimport sys\n"
    fs = astlint.lint_source(src, "m.py", rules=("unused-import",))
    p = str(tmp_path / "b.json")
    findings_mod.save_baseline(p, fs)
    doc = json.load(open(p))
    assert [e["code"] for e in doc["findings"]] == ["import sys"]


def test_missing_baseline_file_means_everything_new(tmp_path):
    assert findings_mod.load_baseline(str(tmp_path / "nope.json")) == []
    with pytest.raises(ValueError, match="expected"):
        p = tmp_path / "bad.json"
        p.write_text("[]")
        findings_mod.load_baseline(str(p))


# ---------------------------------------------------------------------------
# --fix: mechanical rewrites only, never introduces findings
# ---------------------------------------------------------------------------


def test_fix_removes_dead_names_and_whole_statements():
    src = textwrap.dedent("""
        import os
        from typing import Dict, List, Optional

        def f(x) -> Optional[Dict]:
            return x
        """)
    new, counts = fixes.fix_source(src, "m.py")
    assert counts["unused_imports_removed"] == 2   # os, List
    assert "import os" not in new
    assert "from typing import Dict, Optional" in new
    assert astlint.lint_source(new, "m.py", rules=("unused-import",)) == []


def test_fix_normalizes_suppression_spelling():
    src = "import os  #jaxcheck:disable = unused-import , host-sync\n"
    new, n = fixes.normalize_suppressions(src)
    assert n == 1
    assert "# jaxcheck: disable=unused-import,host-sync" in new
    # Canonical spelling is a fixed point.
    again, n2 = fixes.normalize_suppressions(new)
    assert n2 == 0 and again == new


def test_fix_normalize_preserves_trailing_reason():
    src = "x = 1  #jaxcheck:disable = f64-literal -- host accumulation\n"
    new, n = fixes.normalize_suppressions(src)
    assert n == 1
    assert ("# jaxcheck: disable=f64-literal -- host accumulation"
            in new)
    again, n2 = fixes.normalize_suppressions(new)
    assert n2 == 0 and again == new


def test_fix_normalize_leaves_strings_alone_and_keeps_indent():
    # Directive-looking text inside a docstring/string is content the
    # fixer must never rewrite; indented standalone comments keep their
    # indentation.
    src = ('def f():\n'
           '    """normalize ``#jaxcheck:disable = x`` spellings."""\n'
           '    #jaxcheck:disable = host-sync -- why\n'
           '    return 1\n')
    new, n = fixes.normalize_suppressions(src)
    assert n == 1
    assert '``#jaxcheck:disable = x``' in new          # string untouched
    assert '    # jaxcheck: disable=host-sync -- why\n' in new
    again, n2 = fixes.normalize_suppressions(new)
    assert n2 == 0 and again == new


def test_fix_file_is_idempotent(tmp_path):
    p = tmp_path / "m.py"
    p.write_text("import os\nimport sys\n\nprint(sys.path)\n")
    res1 = fixes.fix_file(str(p), repo_root=str(tmp_path))
    assert res1["changed"] and res1["unused_imports_removed"] == 1
    res2 = fixes.fix_file(str(p), repo_root=str(tmp_path))
    assert not res2["changed"]
    assert "import os" not in p.read_text()


# ---------------------------------------------------------------------------
# CLI driver: seeded AST violation → exit 1; clean target → exit 0
# ---------------------------------------------------------------------------


def _run_jaxcheck(args):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "jaxcheck.py"),
         *args], capture_output=True, text=True, env=env, cwd=REPO)


def test_cli_exits_nonzero_on_seeded_ast_violation(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(textwrap.dedent("""
        import jax

        @jax.jit
        def f(x):
            return float(x)
        """))
    proc = _run_jaxcheck(["--ast-only", "--baseline", "", str(bad)])
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "host-sync" in proc.stdout
    good = tmp_path / "good.py"
    good.write_text("def f(x):\n    return x\n")
    proc = _run_jaxcheck(["--ast-only", "--baseline", "", str(good)])
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_cli_update_baseline_then_clean(tmp_path):
    bad = tmp_path / "mod.py"
    bad.write_text("import os\n")
    base = tmp_path / "baseline.json"
    proc = _run_jaxcheck(["--ast-only", "--baseline", str(base),
                          "--update-baseline", str(bad)])
    assert proc.returncode == 0, proc.stdout + proc.stderr
    # Baselined now: same target exits clean, finding reported as baseline.
    proc = _run_jaxcheck(["--ast-only", "--baseline", str(base), str(bad)])
    assert proc.returncode == 0
    assert "1 baselined" in proc.stdout


def test_cli_update_baseline_refuses_disabled_baseline(tmp_path):
    # `--baseline ''` disables baselining; combining it with
    # --update-baseline must be a usage error, NOT a silent rewrite of the
    # committed default baseline.
    mod = tmp_path / "m.py"
    mod.write_text("import os\n")
    proc = _run_jaxcheck(["--ast-only", "--baseline", "",
                          "--update-baseline", str(mod)])
    assert proc.returncode == 2, proc.stdout + proc.stderr
    assert "conflicts" in proc.stderr


def test_cli_only_selector_flag_validation(tmp_path):
    # All usage errors, caught by argparse before any jax import: an
    # unknown section, --ast-only fighting --only, lint targets passed to
    # a pass that never lints, and --update-baseline without an AST pass.
    proc = _run_jaxcheck(["--only", "bogus"])
    assert proc.returncode == 2, proc.stdout + proc.stderr
    proc = _run_jaxcheck(["--ast-only", "--only", "collectives"])
    assert proc.returncode == 2 and "conflicts" in proc.stderr
    proc = _run_jaxcheck(["--only", "collectives", str(tmp_path)])
    assert proc.returncode == 2 and "lint targets" in proc.stderr
    proc = _run_jaxcheck(["--only", "collectives", "--update-baseline",
                          "--baseline", str(tmp_path / "b.json")])
    assert proc.returncode == 2 and "AST pass" in proc.stderr
    proc = _run_jaxcheck(["--fix", "--only", "collectives"])
    assert proc.returncode == 2 and "--fix needs the AST pass" in proc.stderr
    # The wal pass (pass 5) takes no lint targets and never lints.
    proc = _run_jaxcheck(["--only", "wal", str(tmp_path)])
    assert proc.returncode == 2 and "lint targets" in proc.stderr
    proc = _run_jaxcheck(["--fix", "--only", "wal"])
    assert proc.returncode == 2 and "--fix needs the AST pass" in proc.stderr
    # --ast-only is still the working shorthand for --only ast.
    good = tmp_path / "good.py"
    good.write_text("def f(x):\n    return x\n")
    proc = _run_jaxcheck(["--ast-only", "--only", "ast", "--baseline", "",
                          str(good)])
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_cli_rejects_nonexistent_lint_target(tmp_path):
    # A typo'd path must be a usage error (exit 2), never a vacuous pass.
    proc = _run_jaxcheck(["--ast-only", "--baseline", "",
                          str(tmp_path / "no_such_dir")])
    assert proc.returncode == 2, proc.stdout + proc.stderr
    assert "do not exist" in proc.stderr
    with pytest.raises(FileNotFoundError, match="do not exist"):
        report_mod.run_ast_pass(paths=[str(tmp_path / "nope.py")],
                                baseline_path="")


def test_repo_is_lint_clean_in_process():
    # The committed state of the default target set must stay clean — the
    # same verdict `python tools/jaxcheck.py --ast-only` gives CI.
    res = report_mod.run_ast_pass()
    assert res["summary"]["new"] == 0, [
        f.format() for f in res["findings"] if f.is_new]


# ---------------------------------------------------------------------------
# Pass 2 — seeded violations of each contract class (synthetic programs)
# ---------------------------------------------------------------------------


def _program(name, jaxpr, **kw):
    from p2p_tpu.analysis.contracts import Program
    kw.setdefault("group_batch", 2)
    kw.setdefault("gate", None)
    kw.setdefault("metrics", False)
    return Program(name=name, jaxpr=jaxpr, **kw)


def test_no_f64_contract_catches_seeded_promotion():
    import jax
    import jax.numpy as jnp

    from p2p_tpu.analysis.contracts import check_no_f64

    with jax.enable_x64(True):
        bad = jax.make_jaxpr(lambda x: x.astype(jnp.float64))(
            jnp.zeros(3, jnp.float32))
    good = jax.make_jaxpr(lambda x: x * 2)(jnp.zeros(3, jnp.float32))
    res = check_no_f64([_program("seeded/f64", bad),
                        _program("seeded/ok", good)])
    by = {r.program: r for r in res}
    assert not by["seeded/f64"].ok and "f64" in by["seeded/f64"].detail
    assert by["seeded/ok"].ok


def test_hot_scan_callback_contract_catches_io_callback():
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import io_callback

    from p2p_tpu.analysis.contracts import check_hot_scan_callbacks

    def noisy_body(c, x):
        io_callback(lambda v: None, None, x)
        return c + x, x

    def clean_body(c, x):
        return c + x, x

    xs = jnp.zeros(4)
    noisy = jax.make_jaxpr(lambda xs: lax.scan(noisy_body, 0.0, xs))(xs)
    clean = jax.make_jaxpr(lambda xs: lax.scan(clean_body, 0.0, xs))(xs)
    res = check_hot_scan_callbacks([
        _program("serve/bucket1", noisy),    # serve scans are hot end-to-end
        _program("serve/bucket2", clean),
    ])
    by = {r.program: r for r in res}
    assert not by["serve/bucket1"].ok
    assert "callback" in by["serve/bucket1"].detail
    assert by["serve/bucket2"].ok
    # With telemetry on, io_callback is still alien — only debug_callback
    # (the obs sink channel) is allowed in a hot scan.
    res_m = check_hot_scan_callbacks(
        [_program("serve/bucket1", noisy, metrics=True)])
    assert not res_m[0].ok and "io_callback" in res_m[0].detail


def test_phase2_footprint_contract_catches_single_scan_gated_program():
    import jax
    import jax.numpy as jnp
    from jax import lax

    from p2p_tpu.analysis.contracts import check_phase2_footprint

    one_scan = jax.make_jaxpr(
        lambda xs: lax.scan(lambda c, x: (c + x, x), 0.0, xs))(jnp.zeros(3))
    res = check_phase2_footprint(
        [_program("text2image/gated", one_scan, gate=2)])
    assert len(res) == 1 and not res[0].ok
    assert "two-phase" in res[0].detail


def test_doubled_and_folded_batch_detectors():
    from p2p_tpu.analysis.jaxpr_walk import (doubled_batch_shapes,
                                             folded_batch_shapes)

    shapes = [(4, 8, 8, 32),      # 2B=4 feature map → hit
              (2, 8, 8, 32),      # B: fine
              (4, 16),            # 2-D: never a hit
              (3, 4, 8, 8, 32),   # (G, 2B, h, w, c) with lead_dims=(3,)
              (4, 64, 128)]       # token-major (2B, P, C)
    assert doubled_batch_shapes(shapes, 2) == [
        (4, 8, 8, 32), (4, 64, 128)]
    assert doubled_batch_shapes(shapes, 2, max_tokens=32) == [(4, 8, 8, 32)]
    assert doubled_batch_shapes(shapes, 2, lead_dims=(3,)) == [
        (3, 4, 8, 8, 32)]
    assert folded_batch_shapes(shapes, 4) == [(4, 8, 8, 32)]
    assert folded_batch_shapes([(4, 3, 3, 8, 8)], 4) == []   # 5-D: not conv


def test_canonical_contracts_hold_on_session_pipeline(tiny_pipe):
    from p2p_tpu.analysis.contracts import run_contracts

    results = run_contracts(tiny_pipe, buckets=(1,))
    bad = [r.format() for r in results if not r.ok]
    assert not bad, bad
    # The suite must actually cover each contract class.
    kinds = {r.contract for r in results}
    assert kinds == {"no-f64", "hot-scan-callbacks", "phase2-footprint",
                     "donation-as-declared", "trace-invisible",
                     "no-materialized-probs"}
    # ... and the kernel-bearing twins must be in the canonical sweep.
    kernel_progs = {r.program for r in results
                    if r.contract == "no-materialized-probs"}
    assert kernel_progs == {"kernel/ungated-fused", "kernel/gated-fused",
                            "kernel/serve-bucket1-fused"}


def test_no_materialized_probs_holds_on_kernel_twins(tiny_pipe):
    """ISSUE 16: every fused canonical twin carries ZERO CFG-doubled
    attention-probability softmaxes, and every materialized twin (same
    controller, ``kernels=None``) carries one per touched site — the
    detector is never vacuous."""
    from p2p_tpu.analysis.contracts import (_materialized_probs_eqns,
                                            check_no_materialized_probs,
                                            kernel_programs)

    progs = kernel_programs(tiny_pipe)
    res = check_no_materialized_probs(progs)
    assert res and all(r.ok for r in res), [r.format() for r in res]
    by = {p.name: p for p in progs}
    # The full-coverage kernel controller touches all 14 TINY sites; the
    # materialized twin softmaxes every one of them at (2B, heads, P, K).
    assert len(_materialized_probs_eqns(by["kernel/ungated"])) == 14
    assert _materialized_probs_eqns(by["kernel/ungated-fused"]) == []


def test_no_materialized_probs_contract_flips_on_seeded_violation(tiny_pipe):
    """Verdict-flip proof for the kernel contract: presenting the
    materialized trace AS the fused program (the regression where dispatch
    silently stops routing to the kernel) fails naming the shapes; a twin
    that shows no probs fails as a vacuous detector; a fused program with
    no twin fails outright."""
    from p2p_tpu.analysis.contracts import (Program,
                                            _kernel_controller,
                                            _trace_denoise,
                                            check_no_materialized_probs)
    from p2p_tpu.kernels import KernelConfig

    ctrl = _kernel_controller(tiny_pipe)
    mat = _trace_denoise(tiny_pipe, ctrl, gate=None, metrics=False)
    fus = _trace_denoise(tiny_pipe, ctrl, gate=None, metrics=False,
                         kernels=KernelConfig(interpret=True))
    b = 2

    def prog(name, jaxpr):
        return Program(name, jaxpr, group_batch=b, gate=None, metrics=False)

    # Seeded violation: the "fused" program actually materializes.
    res = check_no_materialized_probs(
        [prog("kernel/ungated", mat), prog("kernel/ungated-fused", mat)])
    assert len(res) == 1 and not res[0].ok
    assert "still materializes" in res[0].detail
    # Vacuous witness: the twin shows no probs → hard fail, not a pass.
    res = check_no_materialized_probs(
        [prog("kernel/ungated", fus), prog("kernel/ungated-fused", fus)])
    assert len(res) == 1 and not res[0].ok
    assert "vacuous" in res[0].detail
    # Missing twin → hard fail.
    res = check_no_materialized_probs([prog("kernel/ungated-fused", fus)])
    assert len(res) == 1 and not res[0].ok
    assert "no materialized twin" in res[0].detail


def test_trace_invisible_covers_every_canonical_program(tiny_pipe):
    """The flight-tracing disabled-invisible sweep (ISSUE 7): every
    canonical program's fingerprint is identical with a live tracer."""
    from p2p_tpu.analysis.contracts import (canonical_programs,
                                            check_trace_invisible)

    results = check_trace_invisible(tiny_pipe, buckets=(1,))
    assert all(r.ok for r in results), [r.format() for r in results]
    names = {p.name for p in canonical_programs(tiny_pipe, buckets=(1,))}
    assert {r.program for r in results} == names


def test_trace_invisible_flags_a_tracer_dependent_program(tiny_pipe):
    """Verdict-flip proof: a program whose jaxpr DEPENDS on the flight
    layer's state (the regression this contract exists for) is a hard
    error naming exactly that program."""
    import jax
    import jax.numpy as jnp

    from p2p_tpu.analysis.contracts import Program, check_trace_invisible

    state = {"live": False}

    def poisoned_programs(pipe, buckets=(1,), metrics=False):
        # First call = the quiescent baseline; second call (under the live
        # tracer) grows an extra op — exactly what "tracing on changed the
        # program" looks like.
        def f(x):
            return x * 2 + 1 if state["live"] else x * 2

        jaxpr = jax.make_jaxpr(f)(jnp.float32(1.0))
        state["live"] = True
        return [Program("probe", jaxpr, group_batch=1, gate=None,
                        metrics=metrics)]
    results = check_trace_invisible(tiny_pipe, buckets=(1,),
                                    programs_fn=poisoned_programs)
    assert len(results) == 1 and not results[0].ok
    assert results[0].program == "probe"
    assert "fingerprint changed" in results[0].detail


def test_donation_sweep_covers_pool_and_mesh_programs(tiny_pipe):
    """ISSUE 11 satellite: donation-as-declared extends past text2image/
    sweep to the phase-1/phase-2 pool programs and all three mesh twins —
    every declared name lowers and holds."""
    from p2p_tpu.analysis.contracts import DECLARED_DONATION, check_donation

    res = check_donation(tiny_pipe)
    assert {r.program for r in res} == set(DECLARED_DONATION)
    assert {"sweep/phase1", "sweep/phase2", "sweep/mesh",
            "sweep/phase1-mesh", "sweep/phase2-mesh"} <= set(
                DECLARED_DONATION)
    assert all(r.ok for r in res), [r.format() for r in res]


def test_donation_verdict_flips_both_directions():
    """Seeded proof that the donation contract actually bites, in both
    directions, plus the stale-name leg."""
    from p2p_tpu.analysis.contracts import check_donation

    # Declared-but-absent: the declaration says arg 0 donates, the
    # lowering carries no donor annotations.
    res = check_donation(declared={"sweep/phase1": (0,)},
                         lowerings={"sweep/phase1": "module @jit_f {}"})
    assert len(res) == 1 and not res[0].ok
    assert "0 donated param(s) in lowering, 1 declared" in res[0].detail
    # Applied-but-undeclared: the lowering donates, the declaration is ().
    res = check_donation(
        declared={"sweep/phase2": ()},
        lowerings={"sweep/phase2":
                   'tensor<4xf32> {jax.buffer_donor = true}'})
    assert len(res) == 1 and not res[0].ok
    # A declared name the sweep no longer lowers is an error, not a skip.
    res = check_donation(declared={"ghost": ()}, lowerings={"sweep": ""})
    assert len(res) == 1 and not res[0].ok
    assert "no lowering" in res[0].detail


# ---------------------------------------------------------------------------
# Compile-key completeness (the acceptance regression)
# ---------------------------------------------------------------------------


def test_compile_key_sweep_passes_on_real_schema(tiny_pipe):
    from p2p_tpu.analysis.compile_key import check_compile_key

    # Two known program-changing fields + two known key-neutral fields: a
    # fast slice proving both directions on the real Request schema (the
    # full 18-field sweep runs in tools/jaxcheck.py and the quality gate).
    verdicts = check_compile_key(
        tiny_pipe, fields=["steps", "gate", "seed", "guidance"])
    assert all(v.ok for v in verdicts), [v.format() for v in verdicts]
    by = {v.field: v for v in verdicts}
    assert by["steps"].program_changed and by["steps"].key_changed
    assert by["gate"].program_changed and by["gate"].key_changed
    assert not by["seed"].program_changed and not by["seed"].key_changed
    assert not by["guidance"].program_changed


def test_compile_key_sweep_catches_masked_field(tiny_pipe):
    # THE regression this checker exists for: mask a jaxpr-affecting
    # component (the gate step) out of the key under test and the sweep
    # must flag cache poisoning for exactly that field.
    from p2p_tpu.analysis.compile_key import check_compile_key

    def masked_key(prep):
        (kind, steps, sched, _gate, lanes, treedef,
         reuse_tbl) = prep.compile_key
        return (kind, steps, sched, lanes, treedef, reuse_tbl)

    verdicts = check_compile_key(tiny_pipe, key_fn=masked_key,
                                 fields=["gate", "steps"])
    by = {v.field: v for v in verdicts}
    assert not by["gate"].ok
    assert "poisoning" in by["gate"].problem
    assert by["steps"].ok    # steps still present in the masked key


def test_phase_key_sweep_passes_and_pools_across_modes(tiny_pipe):
    """ISSUE 6: the split per-phase pool keys hold both directions on the
    real schema — and prove the pooling claim: `mode` changes the phase-1
    program+key but neither the phase-2 program nor its key (replace and
    refine edits share one phase-2 pool)."""
    from p2p_tpu.analysis.compile_key import check_phase_keys

    verdicts = check_phase_keys(
        tiny_pipe, fields=["gate", "steps", "mode", "seed"])
    assert all(v.ok for v in verdicts), [v.format() for v in verdicts]
    by = {v.field: v for v in verdicts}
    for phase in ("phase1", "phase2"):
        assert by[f"gate@{phase}"].program_changed
        assert by[f"gate@{phase}"].key_changed
        assert not by[f"seed@{phase}"].program_changed
    assert by["mode@phase1"].program_changed and \
        by["mode@phase1"].key_changed
    assert not by["mode@phase2"].program_changed
    assert not by["mode@phase2"].key_changed


def test_phase_key_sweep_catches_masked_gate(tiny_pipe):
    """THE hand-off regression (ISSUE 6 satellite): a gate-position change
    that alters the phase-2 program but not its key must be a hard error
    — pool-cache poisoning would serve a request the wrong tail program."""
    from p2p_tpu.analysis.compile_key import check_phase_keys

    def masked_key2(prep):
        (tag, name, steps, sched, _gate, lanes, sig,
         reuse_tbl) = prep.phase2_key
        return (tag, name, steps, sched, lanes, sig, reuse_tbl)

    verdicts = check_phase_keys(tiny_pipe, key2_fn=masked_key2,
                                fields=["gate", "steps"])
    by = {v.field: v for v in verdicts}
    assert not by["gate@phase2"].ok
    assert "poisoning" in by["gate@phase2"].problem
    assert by["gate@phase1"].ok       # phase-1 key untouched
    assert by["steps@phase2"].ok      # steps still present in the mask


def test_pool_footprint_contract_fires_on_cfg_doubled_phase2(tiny_pipe):
    """The paired pool contract: a phase-2 'pool program' that still
    carries the CFG-doubled batch (e.g. someone wires the phase-1 program
    in for both pools) must fail phase2-footprint."""
    from p2p_tpu.analysis.contracts import (GATE, _trace_sweep_phase1,
                                            _trace_sweep_phase2,
                                            check_pool_footprint)
    from p2p_tpu.analysis.contracts import _edit_controller

    ctrl = _edit_controller(tiny_pipe)
    p1 = _trace_sweep_phase1(tiny_pipe, ctrl, bucket=1, gate=GATE,
                             metrics=False)
    p2 = _trace_sweep_phase2(tiny_pipe, ctrl, bucket=1, gate=GATE,
                             metrics=False)
    ok = check_pool_footprint([
        _program("serve/phase1-bucket1", p1, gate=GATE, lead_dims=(1,)),
        _program("serve/phase2-bucket1", p2, gate=GATE, lead_dims=(1,))])
    assert len(ok) == 1 and ok[0].ok, ok[0].format()
    # Seeded violation: the phase-1 program posing as the phase-2 pool.
    bad = check_pool_footprint([
        _program("serve/phase1-bucket1", p1, gate=GATE, lead_dims=(1,)),
        _program("serve/phase2-bucket1", p1, gate=GATE, lead_dims=(1,))])
    assert len(bad) == 1 and not bad[0].ok
    assert "2B tensors" in bad[0].detail or "not smaller" in bad[0].detail
    # A missing twin is an error, not a silent skip.
    orphan = check_pool_footprint([
        _program("serve/phase1-bucket1", p1, gate=GATE, lead_dims=(1,))])
    assert len(orphan) == 1 and not orphan[0].ok
    assert "no phase-2 twin" in orphan[0].detail


def test_compile_key_sweep_refuses_uncovered_schema_fields(tiny_pipe,
                                                           monkeypatch):
    # A Request field with no sweep variant must be a hard error — new
    # schema fields cannot dodge the checker by omission.
    from p2p_tpu.analysis import compile_key as ck

    original = dict(ck.VARIANTS)
    trimmed = {k: v for k, v in original.items() if k != "gate"}
    monkeypatch.setattr(ck, "VARIANTS", trimmed)
    with pytest.raises(ValueError, match="gate.*no compile-key sweep"):
        ck.check_compile_key(tiny_pipe, fields=["steps"])
    # And a stale variant for a removed field errors the other way.
    monkeypatch.setattr(ck, "VARIANTS", dict(original, ghost=(1, {})))
    with pytest.raises(ValueError, match="ghost.*no longer"):
        ck.check_compile_key(tiny_pipe, fields=["steps"])


# ---------------------------------------------------------------------------
# Report assembly + gate verdict
# ---------------------------------------------------------------------------


def test_report_verdict_flips_on_contract_class_violation(tmp_path,
                                                          monkeypatch):
    # The exit code is `0 if report["ok"] else 1` (tools/jaxcheck.py), and
    # the AST leg of that mapping is covered by the subprocess test above.
    # This closes the contract leg: a failing contract (or compile-key
    # verdict) must flip run_all's verdict even with a clean AST pass.
    from p2p_tpu.analysis.compile_key import ContentVerdict, FieldVerdict
    from p2p_tpu.analysis.contracts import ContractResult

    def seeded_failure(*a, **kw):
        return {
            "contracts": {"results": [ContractResult(
                "hot-scan-callbacks", "serve/bucket1", False,
                "scan0: 1 callback(s) with telemetry off")], "ok": False},
            "compile_key": {"fields": [FieldVerdict(
                "gate", program_changed=True, key_changed=False)],
                "ok": False},
            "content_key": {"fields": [ContentVerdict(
                "seed", output_determining=True, key_changed=False)],
                "ok": False},
        }

    def clean_collectives(*a, **kw):
        return {"collectives": {"results": [], "ok": True, "table": {}}}

    def clean_cost(*a, **kw):
        return {"cost": {"programs": {}, "budget": [], "ok": True}}

    def clean_wal(*a, **kw):
        # The real pass is exercised by test_report_wal_section below and
        # tests/test_walcheck.py; stubbed here to keep this test on the
        # contract leg (and off the ~7 s model check).
        return {"wal": {"protocol": [],
                        "model": {"scope": "stub", "traces": 0,
                                  "crash_points": 0, "violations": [],
                                  "kinds": [], "kinds_missing": [],
                                  "windows": [], "windows_missing": [],
                                  "ok": True},
                        "ok": True}}

    monkeypatch.setattr(report_mod, "run_contract_pass", seeded_failure)
    monkeypatch.setattr(report_mod, "run_collectives_pass",
                        clean_collectives)
    monkeypatch.setattr(report_mod, "run_cost_pass", clean_cost)
    monkeypatch.setattr(report_mod, "run_wal_pass", clean_wal)
    clean = tmp_path / "clean.py"
    clean.write_text("x = 1\n")
    rep = report_mod.run_all(paths=[str(clean)], baseline_path="")
    assert rep["ok"] is False
    text = report_mod.render_text(rep)
    assert "FAILED" in text and "poisoning" in text
    assert "served another request's images" in text  # content-key leg


def test_report_ok_verdict_and_json_shape(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("import os\n")
    rep = report_mod.run_all(paths=[str(bad)], baseline_path="",
                             ast_only=True)
    assert rep["ok"] is False and rep["ast"]["summary"]["new"] == 1
    doc = report_mod.to_json_dict(rep)
    json.dumps(doc)   # serializable
    assert doc["ast"]["findings"][0]["rule"] == "unused-import"
    clean = tmp_path / "clean.py"
    clean.write_text("x = 1\n")
    rep2 = report_mod.run_all(paths=[str(clean)], baseline_path="",
                              ast_only=True)
    assert rep2["ok"] is True
    assert "PASSED" in report_mod.render_text(rep2)
    assert "FAILED" in report_mod.render_text(rep)


def test_lint_unregistered_journal_record_fire_and_no_fire():
    # Fire: a journal-named receiver writing kind literals outside the
    # registry — both the append (record) and event shapes.
    fired = astlint.lint_source(textwrap.dedent("""
        def f(journal):
            journal.append({"type": "bogus_kind", "vnow": 1})
            journal.event("bogus_event", reason="x")
        """), "p2p_tpu/serve/x.py",
        rules=("unregistered-journal-record",))
    assert [f.line for f in fired] == [3, 4]
    assert "RECORD kind" in fired[0].message
    assert "EVENT kind" in fired[1].message
    # No fire: registered kinds, non-literal kinds (the write-time raise
    # owns those), non-dict records, and non-journal receivers — the obs
    # flight recorder has its own ``.event(...)`` API that must not match.
    clean = astlint.lint_source(textwrap.dedent("""
        def f(journal, shard_journal, flight, kind, rec):
            journal.append({"type": "admitted", "vnow": 1})
            shard_journal.event("degrade", level=1)
            journal.event(kind)
            journal.append(rec)
            flight.event("anything_goes")
        """), "p2p_tpu/serve/x.py",
        rules=("unregistered-journal-record",))
    assert clean == []


def test_report_wal_section_shape_render_and_json(tmp_path):
    # The real pass 5, end to end through the report plumbing: version 3,
    # the wal section's verdict, the render and the JSON round-trip. The
    # model/seeded internals are pinned in tests/test_walcheck.py.
    assert report_mod.REPORT_VERSION == 3
    assert report_mod.SECTIONS[-1] == "wal"
    rep = report_mod.run_wal_pass()
    w = rep["wal"]
    assert w["ok"] is True
    assert [v.check for v in w["protocol"]] == [
        "record-kinds-registered", "event-kinds-registered",
        "append-sites-declared", "replay-branches-declared",
        "chaos-windows-covered"]
    assert w["model"]["violations"] == []
    assert w["model"]["crash_points"] > 1_000
    assert all(f["flipped"] for f in w["seeded"])
    full = {"version": report_mod.REPORT_VERSION, "ok": True,
            "sections": ("wal",), **rep}
    text = report_mod.render_text(full)
    assert "WAL protocol pass: 0 sweep failure(s)" in text
    assert "seeded bug dropped-fsync: flips" in text
    doc = report_mod.to_json_dict(full)
    json.dumps(doc)   # serializable
    assert doc["wal"]["protocol"][0]["ok"] is True
    assert doc["wal"]["model"]["ok"] is True
