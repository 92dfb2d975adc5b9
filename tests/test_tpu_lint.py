"""tpu-lint (paddle_tpu.analysis) test suite.

Covers: the fixture corpus (>= 1 known-bad + known-good file per rule
A1-A5 and B1-B5), the lint-clean-at-HEAD gate over the whole package
(with the <60 s CPU budget), the A3 VMEM estimator cross-checked
against the chip-validated block picks in flash_attention.py /
fused_norm.py, escape hatches, the CLI contract (exit codes, JSON
schema incl. per-pack summaries, rule filters + `B*` pack globs), the
B2 protocol gate against the real worker/procfleet pair, and the A5
runtime promotions recorded by dy2static and the collective layer.
"""
import json
import os
import shutil
import subprocess
import sys
import time
import warnings
from types import SimpleNamespace

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import analysis
from paddle_tpu.analysis import purity, vmem

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXDIR = os.path.join(REPO, "tests", "lint_fixtures")
CLI = os.path.join(REPO, "tools", "tpu_lint.py")

# fixture file -> the ONLY rule it must trip
BAD_FIXTURES = {
    "bad_a1_index_map.py": "A1",
    "bad_a2_blockspec.py": "A2",
    "bad_a3_vmem.py": "A3",
    "bad_a3_quant.py": "A3",
    "bad_a3_optimizer.py": "A3",
    "bad_a3_lora.py": "A3",
    "bad_a4_runtime.py": "A4",
    "bad_a4_decode_loop.py": "A4",
    "bad_a5_purity.py": "A5",
    "bad_b1_cachekey.py": "B1",
    "bad_b1_forwarded.py": "B1",
    "bad_b2_protocol.py": "B2",
    "bad_b3_faultpoint.py": "B3",
    "bad_b4_refusal.py": "B4",
    "bad_b5_metric.py": "B5",
}
GOOD_FIXTURES = [
    "good_a1_index_map.py",
    "good_a2_blockspec.py",
    "good_a3_vmem.py",
    "good_a3_quant_hint.py",
    "good_a3_optimizer.py",
    "good_a3_lora.py",
    "good_a4_runtime.py",
    "good_a4_decode_loop.py",
    "good_a5_purity.py",
    "good_b1_cachekey.py",
    "good_b1_forwarded.py",
    "good_b2_protocol.py",
    "good_b3_faultpoint.py",
    "good_b4_refusal.py",
    "good_b5_metric.py",
]


# ------------------------------------------------------------ fixtures
@pytest.mark.parametrize("fname,rule", sorted(BAD_FIXTURES.items()))
def test_bad_fixture_is_flagged(fname, rule):
    diags = analysis.lint_file(os.path.join(FIXDIR, fname), is_test=False)
    assert diags, f"{fname}: linter found nothing"
    assert {d.rule for d in diags} == {rule}, analysis.format_text(diags)
    for d in diags:
        assert d.path.endswith(fname)
        assert d.line > 0 and d.message and d.hint


@pytest.mark.parametrize("fname", GOOD_FIXTURES)
def test_good_fixture_is_clean(fname):
    diags = analysis.lint_file(os.path.join(FIXDIR, fname), is_test=False)
    assert not diags, analysis.format_text(diags)


def test_every_rule_has_bad_and_good_fixture():
    covered = set(BAD_FIXTURES.values())
    assert covered == {r.id for r in analysis.all_rules()}
    assert len(GOOD_FIXTURES) >= len(covered)


# ------------------------------------------------- lint-clean-at-HEAD
def test_package_is_lint_clean_within_budget():
    t0 = time.perf_counter()
    diags, nfiles = analysis.lint_paths([os.path.join(REPO, "paddle_tpu")])
    dt = time.perf_counter() - t0
    assert nfiles > 200
    assert not diags, "tree must land lint-clean:\n" \
        + analysis.format_text(diags)
    assert dt < 60.0, f"lint of the package took {dt:.1f}s (budget 60s)"


# ------------------------------------------------- A3 VMEM cross-check
class TestVmemCrossCheck:
    """The estimator's verdicts must agree with what the chip actually
    accepted/rejected in round 4 (CLAUDE.md notes, kernel docstrings)."""

    def test_rms_oom_config_flagged(self):
        # chip failure: block_rows=256 @ H=4096 fp32 -> "scoped vmem
        # 24.2M > 16M"; the model must land in that ballpark AND flag it
        blocks = [((256, 4096), "float32")]
        fits, est = vmem.fits_vmem(blocks, blocks)
        assert not fits
        assert 20e6 < est < 28e6, est

    def test_committed_rms_pick_passes(self):
        from paddle_tpu.kernels.fused_norm import pick_block_rows
        br = pick_block_rows(4096, 4096)
        assert br == 64  # the shrink loop's H=4096 answer
        fits, est = vmem.fits_vmem([((br, 4096), "float32")],
                                   [((br, 4096), "float32")])
        assert fits, est

    def test_rms_pick_always_fits_estimator(self):
        # the kernel's guard and the linter's estimator must agree on
        # every shape the guard accepts
        from paddle_tpu.kernels.fused_norm import pick_block_rows
        for h in (128, 1024, 2048, 4096, 8192):
            for has_res in (False, True):
                br = pick_block_rows(8192, h, has_residual=has_res)
                ins = [((br, h), "float32")] * (2 if has_res else 1)
                fits, est = vmem.fits_vmem(ins, [((br, h), "float32")])
                assert fits, (h, has_res, br, est)

    @staticmethod
    def _flash_blocks(bq, bk, D=128):
        from paddle_tpu.kernels.flash_attention import _STATS_LANES
        ins = [((1, bq, D), "bfloat16"), ((1, bk, D), "bfloat16"),
               ((1, bk, D), "bfloat16")]
        outs = [((1, bq, D), "bfloat16"), ((1, 1, bq), "float32")]
        scratch = [((bq, D), "float32"), ((bq, _STATS_LANES), "float32"),
                   ((bq, _STATS_LANES), "float32")]
        # kernel intermediates the specs can't see: fp32 score + prob
        # tiles of (block_q, block_k)
        extra = 2 * bq * bk * 4
        return ins, outs, scratch, extra

    def test_flash_committed_blocks_pass(self):
        from paddle_tpu.kernels.flash_attention import (_pick_block_k,
                                                        _pick_block_q)
        for S in (2048, 8192, 32768):
            bq, bk = _pick_block_q(S), _pick_block_k(S)
            assert bq == bk == 1024  # the on-chip sweep's winner
            ins, outs, scratch, extra = self._flash_blocks(bq, bk)
            fits, est = vmem.fits_vmem(ins, outs, scratch,
                                       extra_bytes=extra)
            assert fits, (S, est)

    def test_flash_2048_blocks_flagged(self):
        # (2048, 2048) "fails to compile (VMEM)" on chip
        # (_pick_block_q docstring) — the estimator must reject it too
        ins, outs, scratch, extra = self._flash_blocks(2048, 2048)
        fits, est = vmem.fits_vmem(ins, outs, scratch, extra_bytes=extra)
        assert not fits
        assert est > vmem.VMEM_BUDGET_BYTES

    # ---- quantized element widths (ISSUE 6) -------------------------
    def test_int8_and_int4_widths(self):
        # an int8 block is budgeted at 1 B/elem, int4 at half that
        # (packed), with the block total rounded UP
        b8, e = vmem._block_bytes(((64, 128), "int8"))
        assert (b8, e) == (64 * 128, 64 * 128)
        b4, _ = vmem._block_bytes(((64, 128), "int4"))
        assert b4 == 64 * 128 // 2
        b4odd, _ = vmem._block_bytes(((1, 3), "int4"))
        assert b4odd == 2          # ceil(1.5)

    def test_quant_matmul_picks_fit_estimator(self):
        # the kernel's own pick function IS the estimator (the A3
        # discipline), so everything it accepts must fit — sweep the
        # serving-relevant decode/verify/prefill shapes
        from paddle_tpu.kernels.quant_matmul import (_blocks,
                                                     pick_quant_blocks)
        for M, K, N in [(1, 4096, 4096), (8, 4096, 11008),
                        (256, 4096, 128256), (32, 8192, 8192)]:
            picked = pick_quant_blocks(M, K, N)
            assert picked is not None, (M, K, N)
            ins, outs, scratch = _blocks(*picked, "float32")
            fits, est = vmem.fits_vmem(ins, outs, scratch)
            assert fits, (M, K, N, picked, est)

    def test_scale_buffer_costs_are_counted(self):
        # the fp32 scale row is tiny but must not be dropped: its bytes
        # appear in the estimate
        base = vmem.estimate_vmem_bytes([((8, 512), "int8")], [])
        with_scale = vmem.estimate_vmem_bytes(
            [((8, 512), "int8"), ((1, 512), "float32")], [])
        assert with_scale == base + 2 * 512 * 4   # double-buffered


def test_a3_dtype_hint_refines_in_spec_widths():
    """The `# tpu-lint-hint: vmem-dtypes=...` comment budgets each
    in_spec at its true width: the good quant fixture passes ONLY
    because of the hint (stripping it false-positives at fp32 width),
    and the hint never amnesties a genuinely oversized block (the bad
    quant fixture stays flagged)."""
    good = os.path.join(FIXDIR, "good_a3_quant_hint.py")
    assert analysis.lint_file(good, is_test=False) == []
    with open(good) as f:
        src = f.read().replace("# tpu-lint-hint: vmem-dtypes="
                               "float32,int8,float32", "")
    diags = analysis.lint_source(src, path="nohint.py", is_test=False)
    assert {d.rule for d in diags} == {"A3"}


# -------------------------------------------------------- escape hatch
_BAD_SPEC_SRC = """
from jax.experimental import pallas as pl
s = pl.BlockSpec((12, 100), lambda i: (i, i)){hatch}
"""


def test_escape_hatch_suppresses_same_line():
    src = _BAD_SPEC_SRC.format(hatch="  # tpu-lint: blockspec-ok")
    assert not analysis.lint_source(src, "snippet.py", is_test=False)


def test_escape_hatch_suppresses_from_previous_line():
    src = "from jax.experimental import pallas as pl\n" \
          "# tpu-lint: blockspec-ok\n" \
          "s = pl.BlockSpec((12, 100), lambda i: (i, i))\n"
    assert not analysis.lint_source(src, "snippet.py", is_test=False)


def test_escape_hatch_is_slug_scoped():
    # an index-map hatch must NOT silence the blockspec findings
    src = _BAD_SPEC_SRC.format(hatch="  # tpu-lint: index-map-ok")
    diags = analysis.lint_source(src, "snippet.py", is_test=False)
    assert {d.rule for d in diags} == {"A2"}


def test_skip_file_hatch():
    src = "# tpu-lint: skip-file\n" + _BAD_SPEC_SRC.format(hatch="")
    assert not analysis.lint_source(src, "snippet.py", is_test=False)


def test_escape_hatch_covers_b_slugs():
    """The B rules honor the same `# tpu-lint: <slug>-ok` hatch
    mechanics as the A pack (same line or the line above)."""
    refusal = ('def configure(a, b):\n'
               '    if a and b:\n'
               '        # tpu-lint: refusal-ok\n'
               '        raise ValueError("a and b are mutually '
               'exclusive")\n')
    assert not analysis.lint_source(refusal, "snippet.py", is_test=False)
    with open(os.path.join(FIXDIR, "bad_b1_cachekey.py")) as f:
        src = f.read()
    hatched = src.replace(
        "        model = self.model",
        "        # tpu-lint: cache-key-ok\n        model = self.model")
    diags = analysis.lint_source(hatched, "snippet.py", is_test=False)
    # the hatch silences ONLY the model line; the sampling axes stay
    assert {d.rule for d in diags} == {"B1"} and len(diags) == 2
    assert not any("self.model" in d.message for d in diags)


def test_b2_catches_deleted_dispatch_arm(tmp_path):
    """The acceptance gate: deleting one handler arm from the REAL
    procfleet dispatch makes B2 fail on the real worker file. Copies of
    the live pair go to tmpdir (outside any checkout, so B3/B5's
    cross-file halves stand down) and the procfleet copy's
    `prefill_done` arm is renamed away."""
    for fn in ("worker.py", "procfleet.py"):
        with open(os.path.join(REPO, "paddle_tpu", "serving", "fleet",
                               fn)) as f:
            src = f.read()
        if fn == "procfleet.py":
            assert 'mtype == "prefill_done"' in src
            src = src.replace('mtype == "prefill_done"',
                              'mtype == "prefill_done_disabled"')
        (tmp_path / fn).write_text(src)
    diags = analysis.lint_file(str(tmp_path / "worker.py"),
                               is_test=False)
    b2 = [d for d in diags if d.rule == "B2"]
    assert any("'prefill_done'" in d.message
               and d.severity == "error" for d in b2), \
        analysis.format_text(diags)
    # the untampered pair is symmetric: no B2 findings on either side
    # (fresh file names sidestep the per-path peer cache)
    for fn in ("worker.py", "procfleet.py"):
        with open(os.path.join(REPO, "paddle_tpu", "serving", "fleet",
                               fn)) as f:
            (tmp_path / ("ok_" + fn)).write_text(
                f.read().replace("protocol-peer=procfleet.py",
                                 "protocol-peer=ok_procfleet.py")
                        .replace("protocol-peer=worker.py",
                                 "protocol-peer=ok_worker.py"))
    for fn in ("ok_worker.py", "ok_procfleet.py"):
        diags = analysis.lint_file(str(tmp_path / fn), is_test=False)
        assert not [d for d in diags if d.rule == "B2"], \
            analysis.format_text(diags)


def test_rule_selection_and_unknown_selector():
    only_a1 = analysis.select_rules(["A1"])
    assert [r.id for r in only_a1] == ["A1"]
    by_slug = analysis.select_rules(["vmem", "index-map"])
    assert {r.id for r in by_slug} == {"A1", "A3"}
    with pytest.raises(ValueError):
        analysis.select_rules(["A9"])
    # "--rules ," must not select NOTHING and pass vacuously
    with pytest.raises(ValueError):
        analysis.select_rules(["", " "])
    # pack globs match rule IDS only: B* is the whole B pack and must
    # NOT surprise-match A2 via its slug "blockspec"
    assert {r.id for r in analysis.select_rules(["B*"])} \
        == {"B1", "B2", "B3", "B4", "B5"}
    assert {r.id for r in analysis.select_rules(["a*"])} \
        == {"A1", "A2", "A3", "A4", "A5"}
    with pytest.raises(ValueError):
        analysis.select_rules(["Z*"])


def test_resolve_int_pow_is_bounded():
    # a typo'd exponent chain must not stall the lint gate
    from paddle_tpu.analysis import astutil
    import ast
    consts = astutil.module_int_consts(
        ast.parse("SMALL = 2 ** 10\nBIG = 10 ** 10 ** 8\n"))
    assert consts.get("SMALL") == 1024
    assert "BIG" not in consts


def test_syntax_error_reports_instead_of_raising():
    diags = analysis.lint_source("def broken(:\n", "x.py", is_test=False)
    assert len(diags) == 1 and diags[0].rule == "parse"


# ---------------------------------------------------------------- CLI
def _run_cli(*args):
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    return subprocess.run([sys.executable, CLI, *args],
                          capture_output=True, text=True, env=env,
                          cwd=REPO, timeout=120)


def test_cli_exits_zero_on_clean_tree():
    r = _run_cli(os.path.join("paddle_tpu", "kernels"))
    assert r.returncode == 0, r.stdout + r.stderr
    assert "0 finding(s)" in r.stdout


def test_cli_flags_bad_snippet_with_json(tmp_path):
    # "make lint exits non-zero when any fixture-bad snippet is
    # introduced": drop a bad fixture into a lintable (non-test) spot
    dst = tmp_path / "snippet_a2.py"
    shutil.copy(os.path.join(FIXDIR, "bad_a2_blockspec.py"), dst)
    r = _run_cli("--json", str(dst))
    assert r.returncode == 1, r.stdout + r.stderr
    payload = json.loads(r.stdout)
    assert payload["version"] == 1
    assert payload["files_scanned"] == 1
    assert payload["findings"], payload
    for f in payload["findings"]:
        assert set(f) >= {"rule", "slug", "severity", "path", "line",
                          "col", "message", "hint", "source"}
        assert f["rule"] == "A2" and f["severity"] == "error"


def test_cli_rule_filter_and_exit_codes(tmp_path):
    dst = tmp_path / "snippet_a2.py"
    shutil.copy(os.path.join(FIXDIR, "bad_a2_blockspec.py"), dst)
    # selecting a rule the snippet doesn't trip -> clean exit
    r = _run_cli("--rules", "A1", str(dst))
    assert r.returncode == 0, r.stdout + r.stderr
    r = _run_cli("--rules", "blockspec", str(dst))
    assert r.returncode == 1
    r = _run_cli("--rules", "NOPE", str(dst))
    assert r.returncode == 2


def test_cli_list_rules():
    r = _run_cli("--list-rules")
    assert r.returncode == 0
    for rid in ("A1", "A2", "A3", "A4", "A5",
                "B1", "B2", "B3", "B4", "B5"):
        assert rid in r.stdout


def test_cli_pack_summary_json_and_text(tmp_path):
    """The per-pack summary is one assertable line: the driver gate
    greps `packs["B"]["summary"]` (JSON) or the `tpu-lint[B]:` line
    (text) instead of re-deriving counts from the findings list."""
    dst = tmp_path / "snippet_b4.py"
    shutil.copy(os.path.join(FIXDIR, "bad_b4_refusal.py"), dst)
    r = _run_cli("--json", str(dst))
    assert r.returncode == 1, r.stdout + r.stderr
    payload = json.loads(r.stdout)
    b = payload["packs"]["B"]
    assert b["rules"] == ["B1", "B2", "B3", "B4", "B5"]
    assert b["findings"] == 3 and b["files"] == 1
    assert b["summary"] == "3 findings, 1 files, 5 rules"
    assert payload["packs"]["A"]["findings"] == 0
    # text mode prints the same summary per pack
    r = _run_cli(str(dst))
    assert "tpu-lint[B]: 3 findings, 1 files, 5 rules" in r.stdout
    assert "tpu-lint[A]: 0 findings, 1 files, 5 rules" in r.stdout
    # a --rules selection narrows the pack bookkeeping with it
    r = _run_cli("--json", "--rules", "B*", str(dst))
    payload = json.loads(r.stdout)
    assert list(payload["packs"]) == ["B"]
    assert payload["packs"]["B"]["summary"] == \
        "3 findings, 1 files, 5 rules"


# ------------------------------------------------ A5 runtime promotion
def test_loop_mutation_decline_records_diagnostic():
    """The dy2static mutation decline (loop kept eager) now surfaces as
    a shared A5 diagnostic with a real file:line."""
    purity.reset()

    def fn(x, n):
        out = []
        s = x * 0.0
        for i in range(n):
            s = s + x
            out.append(1)
        return s, len(out)

    traced = paddle.jit.to_static(fn)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        traced(paddle.to_tensor(np.ones(2, np.float32)),
               paddle.to_tensor(5))
    diags = [d for d in purity.snapshot() if d.slug == "loop-mutation"]
    assert diags, "mutation decline did not record a diagnostic"
    d = diags[0]
    assert d.rule == "A5" and d.source == "runtime"
    assert d.path.endswith("test_tpu_lint.py")
    assert d.line > 0 and "for loop" in d.message
    rep = paddle.jit.to_static_report(reset=True)
    assert any(x["slug"] == "loop-mutation"
               for x in rep["purity_diagnostics"])
    assert not purity.snapshot()  # reset=True drained the recorder


def test_loop_print_warn_records_diagnostic():
    """The scan/while trace-time side-effect warning doubles as an A5
    diagnostic (same event, now reportable)."""
    purity.reset()

    def fn(x):
        s = x * 0.0
        while s.sum() < 10.0:     # tensor predicate -> while_loop
            print("step")
            s = s + x
        return s

    traced = paddle.jit.to_static(fn)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        traced(paddle.to_tensor(np.ones(4, np.float32)))
    assert any("trace time" in str(w.message) for w in caught)
    diags = [d for d in purity.snapshot() if d.slug == "loop-side-effect"]
    assert diags
    assert "print" in diags[0].message
    assert diags[0].severity == "warning"
    purity.reset()


def test_out_of_trace_collective_records_diagnostic():
    from paddle_tpu.distributed import collective as C
    purity.reset()
    g = SimpleNamespace(nranks=2, axis_name="data")
    with pytest.raises(RuntimeError):
        C._require_trace_or_world1("all_reduce", g)
    diags = [d for d in purity.snapshot() if d.slug == "collective"]
    assert diags and diags[0].severity == "error"
    assert "all_reduce" in diags[0].message
    purity.reset()


def test_recorder_dedups_and_is_bounded():
    purity.reset()
    # retraces of the same function re-record the same event: dedup
    for _ in range(5):
        purity.record_out_of_trace_collective("all_reduce", 2, "data")
    assert len(purity.snapshot()) == 1
    # distinct events still accumulate, bounded at 256
    for i in range(300):
        purity.record(analysis.Diagnostic(
            rule="A5", slug="loop-mutation", severity="warning",
            path="f.py", line=i + 1, message=f"m{i}", source="runtime"))
    assert len(purity.snapshot()) == 256
    assert purity.dropped() == 45  # 301 unique - 256 window
    # drain opens a fresh dedup window: recurrence is a new report
    purity.drain()
    purity.record_out_of_trace_collective("all_reduce", 2, "data")
    assert len(purity.snapshot()) == 1
    purity.reset()


def test_hatch_inside_string_literal_does_not_suppress():
    """A docstring/test string QUOTING the hatch syntax must not
    suppress findings (the regex-over-lines bug: this very test file
    was silently skip-file'd by its own embedded fixtures)."""
    src = ('"""docs say: use  # tpu-lint: skip-file  to skip."""\n'
           "from jax.experimental import pallas as pl\n"
           's = "# tpu-lint: blockspec-ok"\n'
           "b = pl.BlockSpec((12, 100), lambda i: (i, i))\n")
    diags = analysis.lint_source(src, "snippet.py", is_test=False)
    assert {d.rule for d in diags} == {"A2"}


def test_this_test_file_is_actually_linted():
    # regression for the skip-file-via-string-literal bug: this file
    # embeds hatch syntax in STRINGS (the fixtures above) and must not
    # parse as hatched — comments only
    from paddle_tpu.analysis import driver as adriver
    with open(os.path.abspath(__file__), encoding="utf-8") as f:
        src = f.read()
    hatches, _hints = adriver._parse_directives(src)
    assert not any("skip-file" in toks for toks in hatches.values())
    assert analysis.lint_file(os.path.abspath(__file__)) == []


def test_fallback_report_lint_section_renders():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import fallback_report as fr
    finally:
        sys.path.pop(0)
    diag = analysis.Diagnostic(
        rule="A5", slug="loop-mutation", severity="warning",
        path="m.py", line=7, message="demo", source="runtime")
    old = dict(fr.REPORTS)
    fr.REPORTS.clear()
    try:
        fr.REPORTS["demo_model"] = {
            "report": {"purity_diagnostics": [diag.to_dict()]},
            "losses": [0.0], "seconds": 0.0}
        lines = fr._lint_section()
        text = "\n".join(lines)
        assert "demo_model" in text and "A5[loop-mutation]" in text \
            and "m.py:7" in text
        fr.REPORTS.clear()
        empty = "\n".join(fr._lint_section())
        assert "No purity diagnostics" in empty
    finally:
        fr.REPORTS.clear()
        fr.REPORTS.update(old)
