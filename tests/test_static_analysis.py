"""tpulint: the project's static-analysis gate, and the gate's own tests.

Three layers:

1. **The real gate** — all eight rules over ``src/python`` + ``tools``
   must be clean (modulo the checked-in baseline, which is kept
   empty).  This is the tier-1 invariant every future PR inherits:
   guarded fields stay locked, nothing blocks under a lock at any call
   depth, deadline math stays monotonic, typed errors stay
   wire-mapped, threads stay daemon-or-joined, fault points stay
   registered, guarded decisions stay inside one critical section, and
   the router stays protocol-identical to the replica surface it
   re-serves.
2. **The fixture suite** — known-bad snippets under
   ``tests/tpulint_fixtures/`` pin each rule's exact ``file:line``
   findings, the suppression comment, and baseline add/expire.
3. **Doc-drift checks** — the resilience doc's fault table must match
   ``faults.POINTS`` and its stats paragraph must document every
   ``DecodeScheduler.stats()`` key.

Plus the gate's own moving parts: the per-file ModuleInfo cache
(cold-vs-warm + mtime invalidation), the tier-1 environmental-noise
ratchet (``tools/t1_noise.py``), and ``tools/check.py
--changed-only``.
"""

import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.lint  # `pytest -m lint` runs just this gate

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC_PY = os.path.join(REPO_ROOT, "src", "python")
TOOLS = os.path.join(REPO_ROOT, "tools")
FIXTURES = os.path.join(REPO_ROOT, "tests", "tpulint_fixtures")
BASELINE = os.path.join(REPO_ROOT, "tools", "tpulint_baseline.txt")
RESILIENCE_MD = os.path.join(REPO_ROOT, "docs", "resilience.md")

from tpulint import RULES_BY_ID, lint_paths  # noqa: E402
from tpulint.findings import apply_baseline  # noqa: E402


def _lint_fixture(subdir, rule, docs_path=None, baseline_path=None):
    result = lint_paths(
        [os.path.join(FIXTURES, subdir)], rules=[rule],
        docs_path=docs_path, baseline_path=baseline_path,
        repo_root=REPO_ROOT)
    return result


def _lines(findings):
    return sorted(f.lineno for f in findings)


# -- layer 1: the real tree is clean -----------------------------------------


def test_real_tree_is_clean_under_all_rules():
    """The tier-1 gate: src/python + tools lint clean (empty
    baseline) under all eight rules — interprocedural ones included."""
    result = lint_paths(
        [SRC_PY, TOOLS], rules=None, baseline_path=BASELINE,
        docs_path=RESILIENCE_MD, repo_root=REPO_ROOT)
    assert not result.new, "new tpulint findings:\n" + "\n".join(
        f.render() for f in result.new)
    assert not result.stale, (
        "stale baseline entries (run tools/tpulint.py --update-baseline): "
        "{}".format(result.stale))


def test_every_rule_ran_over_the_real_tree():
    """All eight rules are registered and selected by default."""
    assert sorted(RULES_BY_ID) == [
        "R1", "R2", "R3", "R4", "R5", "R6", "R7", "R8"]


def test_r8_engages_on_the_real_surfaces():
    """R8 clean must mean "compared and equal", not "never found the
    surfaces" — pin that extraction sees all three real modules and
    their protocol facts (a rename that blinds the rule fails HERE,
    not silently)."""
    from tpulint import rules_protocol as rp
    from tpulint.runner import discover, _analyze_cached, _relpath

    mods = [_analyze_cached(p, _relpath(p, REPO_ROOT))
            for p in discover([SRC_PY])]
    http = router = grpc = None
    for m in mods:
        base = m.relpath.rsplit("/", 1)[-1]
        if base == "http_frontend.py" and rp._has_route_method(m):
            http = m
        elif base == "router.py" and rp._has_route_method(m):
            router = m
        elif base == "grpc_frontend.py" and rp.GRPC_MAP_FUNC in m.func_dicts:
            grpc = m
    assert http is not None and router is not None and grpc is not None
    # the facts each comparison keys on are actually extracted
    assert "/v2/health/stats" in rp._routes(http)
    assert any("generate_stream" in r for r in rp._routes(http))
    assert any("generate_stream" in r for r in rp._routes(router))
    # the telemetry scrape surface is served by BOTH tiers (the router
    # fleet-aggregates it) — the /metrics parity check has real teeth
    assert rp.METRICS_ROUTE in rp._routes(http)
    assert rp.METRICS_ROUTE in rp._routes(router)
    # the admin surface (fleet-supervisor contract) is extracted too:
    # every declared admin route and both membership verbs
    assert set(rp.ROUTER_ADMIN_ROUTES) <= rp._routes(router)
    assert set(rp.MEMBERSHIP_ACTIONS) <= rp._str_constants(router)
    assert rp._sse_id_formats(http) == rp._sse_id_formats(router) != set()
    assert rp._final_markers(http) == rp._final_markers(router) != set()
    assert rp._response_params_keys(mods) >= {"generation_id", "seq"}
    # the status-line map is structurally shared (_http_base) — R8's
    # per-surface map comparison only re-arms on a re-fork
    assert rp._status_map_keys(http) is None
    assert rp._status_map_keys(router) is None


def test_exception_twins_are_one_class():
    """The satellite dedup, runtime-pinned: scheduler and core raise
    the SAME canonical tpuserver.errors classes (historically two
    definitions kept in sync only by convention)."""
    from tpuserver import core, errors, scheduler

    for name in ("DeadlineExceeded", "SlotQuarantined",
                 "UnknownGeneration"):
        canonical = getattr(errors, name)
        assert getattr(scheduler, name) is canonical, name
        assert getattr(core, name) is canonical, name
    assert issubclass(errors.SlotQuarantined, errors.ServerError)
    assert errors.SlotQuarantined("x").code == 422
    assert errors.UnknownGeneration("x").code == 404
    assert errors.DeadlineExceeded("x").code == 504


# -- layer 2: the fixture suite ----------------------------------------------


def test_r1_guarded_by_fixture():
    findings = _lint_fixture("r1", "R1").new
    assert _lines(findings) == [16, 19, 34]
    by_line = {f.lineno: f.message for f in findings}
    assert "written outside" in by_line[16]
    assert "read outside" in by_line[19]
    # the closure case: a callback defined under the lock runs later,
    # without it
    assert "callback()" in by_line[34]
    # the suppressed read (line 25) and the *_locked-convention and
    # Condition-alias accesses produced no findings
    assert all(f.path.endswith("r1/bad.py") for f in findings)


def test_r2_blocking_and_lock_order_fixture():
    findings = _lint_fixture("r2", "R2").new
    assert _lines(findings) == [14, 18, 26, 49, 64]
    by_line = {f.lineno: f.message for f in findings}
    assert "time.sleep" in by_line[14]
    assert "Thread.join" in by_line[18]
    # join(5.0) positionally is a thread join too (str.join never
    # takes a numeric literal); line 30's ",".join stays clean
    assert "Thread.join" in by_line[26]
    assert "lock-acquisition-order cycle" in by_line[49]
    assert "Deadlock._a -> Deadlock._b -> Deadlock._a" in by_line[49]
    # the multi-item form `with self._c, self._d:` acquires
    # sequentially — the c->d edge exists, so reversed nesting cycles
    assert ("MultiItemDeadlock._c -> MultiItemDeadlock._d -> "
            "MultiItemDeadlock._c") in by_line[64]


def test_r3_monotonic_clock_fixture():
    findings = _lint_fixture("r3", "R3").new
    assert _lines(findings) == [6, 10, 11, 13, 28, 29, 39]
    by_line = {f.lineno: f.message for f in findings}
    assert "wall-clock read time.time()" in by_line[6]
    assert "used in a comparison" in by_line[11]
    assert "passed as timeout=" in by_line[13]
    # line 23 (suppressed) and monotonic_is_fine produced nothing;
    # the closure's defect reports EXACTLY once, attributed to the
    # closure's own scope (nested defs are pruned from the outer walk)
    assert "in inner()" in by_line[29]
    # taint tracking walks in document order: an assignment nested two
    # levels deep still taints a shallow sink below it
    assert "passed to .wait()" in by_line[39]


def test_r4_wire_map_fixture():
    findings = _lint_fixture(
        "r4", "R4",
        docs_path=os.path.join(FIXTURES, "r4", "docs.md")).new
    msgs = sorted(f.message for f in findings)
    assert len(findings) == 4
    assert sum("HTTP status map" in m for m in msgs) == 1
    assert sum("gRPC code map" in m for m in msgs) == 1
    assert sum("status table in docs" in m for m in msgs) == 1
    assert sum("duplicate definition" in m for m in msgs) == 1
    # the unmapped code is named, and the twin anchors in twin.py
    assert all("418" in m for m in msgs if "missing" in m)
    twin = [f for f in findings if "duplicate" in f.message][0]
    assert twin.path.endswith("r4/twin.py") and twin.lineno == 4


def test_r4_missing_wire_map_is_a_finding_not_a_skip():
    """Renaming/moving _STATUS_LINE or _status_code must fail the
    gate, not silently disable R4."""
    result = lint_paths(
        [os.path.join(FIXTURES, "r4", "errors_like.py")], rules=["R4"],
        repo_root=REPO_ROOT)
    msgs = [f.message for f in result.new]
    assert len(msgs) == 2
    assert any("no HTTP status map" in m for m in msgs)
    assert any("no gRPC code map" in m for m in msgs)


def test_r5_thread_lifecycle_fixture():
    findings = _lint_fixture("r5", "R5").new
    assert _lines(findings) == [44, 49, 68, 75]
    by_line = {f.lineno: f.message for f in findings}
    # DaemonOwner (daemon=True), JoinedOwner (join(timeout=5)),
    # JoinedPositionalOwner (join(5) positional), and AppendOwner
    # (`self._threads.append(Thread(...))` idiom, joined in close())
    # produced no findings
    assert "daemon=True" in by_line[44]
    assert "daemon=True" in by_line[49]
    # writer-thread companion (ISSUE 18): a `name="*writer*"` thread
    # appends a crash log and needs BOTH halves — GoodWriter (daemon
    # AND joined) is clean; daemon-only drops the queued tail on a
    # clean close, joined-only wedges a crashing owner
    assert "writer thread 'journal-writer'" in by_line[68]
    assert "drain the queued tail" in by_line[68]
    assert "daemon" not in by_line[68].split("missing", 1)[1]
    assert "writer thread 'stats-writer'" in by_line[75]
    assert "daemon=True" in by_line[75]


def test_r6_fault_registry_fixture():
    findings = _lint_fixture("r6", "R6").new
    by_line = {(os.path.basename(f.path), f.lineno): f.message
               for f in findings}
    assert len(findings) == 4
    assert "dead registry entry" in by_line[("faults.py", 6)]
    assert "not registered" in by_line[("site.py", 7)]
    assert "string-literal" in by_line[("site.py", 8)]
    assert "2 sites" in by_line[("site.py", 10)]


def test_r2i_interprocedural_blocking_fixture():
    """R2i: blocking-ness propagates through the call graph, the
    annotation escape hatches are honored, and a two-hop AB/BA
    acquisition split across methods is a cycle."""
    findings = _lint_fixture("r2i", "R2").new
    assert _lines(findings) == [17, 35, 54, 58, 82, 110]
    by_line = {f.lineno: f.message for f in findings}
    # the witness chain names every hop down to the primitive
    assert ("self._helper -> self._nap -> time.sleep"
            in by_line[17])
    assert "DeepBlock.outer()" in by_line[17]
    # `# tpulint: blocks` forces a callee the resolver can't see into
    assert "annotated '# tpulint: blocks'" in by_line[35]
    # `# tpulint: nonblocking` vouched for _bounded_wait: no finding
    # for vouched() even though its callee transitively sleeps
    assert not any("vouched" in f.message for f in findings)
    # blocking-ness is a whole-graph fixpoint: the _head<->_shim cycle
    # must flag BOTH entry sites, including blocked(), whose only
    # callee is the cycle member a per-query memo would have finalized
    # non-blocking while the cycle head was still open
    assert ("self._head -> self._sleepy -> time.sleep"
            in by_line[54])
    assert ("self._shim -> self._head -> self._sleepy -> time.sleep"
            in by_line[58])
    # bare names cross modules ONLY through a `from X import name` in
    # the caller: the helpers.slow_flush import resolves (and blocks),
    # while `unrelated` — imported from an UNANALYZED module but
    # sharing its name with a helpers function — must not bind (a
    # by-name bind would fabricate the witness chain)
    assert "slow_flush -> time.sleep" in by_line[82]
    assert "CrossModule.flush()" in by_line[82]
    assert not any("clean" in f.message for f in findings)
    # the cycle needed TWO hops of resolution (ab -> _mid -> _take_b):
    # one-level resolution could not see it
    assert "lock-acquisition-order cycle" in by_line[110]
    assert ("CrossOrder._a -> CrossOrder._b -> CrossOrder._a"
            in by_line[110])


def test_r7_atomicity_fixture():
    """R7: both torn shapes fire, widened/unrelated critical sections
    stay clean, and the suppression comment works."""
    findings = _lint_fixture("r7", "R7").new
    assert _lines(findings) == [17, 23]
    by_line = {f.lineno: f.message for f in findings}
    # shape B anchors at the store computed from the stale snapshot
    assert "Torn.lost_update()" in by_line[17]
    assert "_count is read under _lock into 'total'" in by_line[17]
    assert "computed from it" in by_line[17]
    # shape A anchors at the re-acquisition inside the stale branch
    assert "Torn.stale_decision()" in by_line[23]
    assert "branch guarding the store to '_state' tests it" in by_line[23]
    # widened_ok / unrelated_ok produced nothing; the suppressed
    # re-acquisition (line 43) is silenced by its disable comment
    assert 43 not in _lines(findings)


def test_r8_protocol_parity_fixture():
    """R8: every drift class between the fixture router and the
    fixture replica surface is a finding — the
    router-vs-frontend divergence cases the real tree must never
    grow."""
    findings = _lint_fixture("r8", "R8").new
    assert len(findings) == 20
    router = [f for f in findings if f.path.endswith("r8/router.py")]
    grpc = [f for f in findings if f.path.endswith("r8/grpc_frontend.py")]
    http = [f for f in findings if f.path.endswith("r8/http_frontend.py")]
    assert len(router) == 17 and len(grpc) == 2 and len(http) == 1
    # surface-level router findings anchor at the route table
    assert all(f.lineno == 5 for f in router + http)
    msgs = sorted(f.message for f in router)
    assert sum("health route" in m for m in msgs) == 2
    assert any("'/v2/health/live'" in m for m in msgs)
    assert any("'/v2/health/stats'" in m for m in msgs)
    assert sum("generate_stream streaming surface" in m
               for m in msgs) == 1
    # the fixture replica serves /metrics, the fixture router does not:
    # the telemetry-parity drift class fires exactly once
    assert sum("'/metrics' telemetry route" in m for m in msgs) == 1
    # the fixture replica serves the shm register/unregister verbs;
    # the fixture router never references them: the broadcast-parity
    # drift class fires exactly once, naming every missing token
    assert sum("shm verb token(s) sharedmemory/register/unregister" in m
               for m in msgs) == 1
    assert sum("verb(s) GET" in m for m in msgs) == 1
    assert sum("missing code(s) 429, 503" in m for m in msgs) == 1
    assert sum("SSE id-line format" in m for m in msgs) == 1
    assert sum("terminal SSE event" in m for m in msgs) == 1
    assert sum("resume-grammar key" in m for m in msgs) == 2
    assert sum("'Last-Event-ID'" in m for m in msgs) == 1
    # the router's own admin surface: /router/stats and
    # /router/partition (the horizontal tier's map/epoch surface)
    # unserved, and the served membership route references neither add
    # nor remove
    assert sum("declared admin route '/router/stats'" in m
               for m in msgs) == 1
    assert sum("declared admin route '/router/partition'" in m
               for m in msgs) == 1
    assert sum("membership action" in m for m in msgs) == 2
    assert sum("checkpoint" in m for m in msgs) == 1  # producer key
    # the replica itself can drift from a producer's published grammar
    assert "checkpoint" in http[0].message
    # HTTP<->gRPC code-map parity anchors at the gRPC map
    grpc_msgs = sorted(f.message for f in grpc)
    assert any("418" in m and "no HTTP status line" in m
               for m in grpc_msgs)
    assert any("503" in m and "no gRPC mapping" in m for m in grpc_msgs)
    assert all(f.lineno == 5 for f in grpc)


def test_r8_partial_runs_stay_quiet():
    """Linting one surface alone skips the comparisons that need its
    peer (file-scoped runs must not fail on absent modules)."""
    result = lint_paths(
        [os.path.join(FIXTURES, "r8", "http_frontend.py")], rules=["R8"],
        repo_root=REPO_ROOT)
    assert result.new == []


def test_suppression_comment_silences_exactly_its_line():
    # r1/bad.py line 25 carries `# tpulint: disable=R1` on a guarded
    # read; the identical unsuppressed read on line 19 still fires
    findings = _lint_fixture("r1", "R1").new
    assert 25 not in _lines(findings)
    assert 19 in _lines(findings)


def test_baseline_grandfathers_and_expires(tmp_path):
    result = _lint_fixture("r1", "R1")
    assert len(result.new) == 3
    # adding the current findings to a baseline silences them ...
    baseline = tmp_path / "baseline.txt"
    baseline.write_text(
        "# comment line\n"
        + "\n".join(f.fingerprint for f in result.new) + "\n")
    rebased = _lint_fixture("r1", "R1", baseline_path=str(baseline))
    assert rebased.new == []
    assert len(rebased.grandfathered) == 3
    assert rebased.stale == []
    # ... and an entry whose finding was fixed reports as stale
    baseline.write_text(
        "\n".join(f.fingerprint for f in result.new)
        + "\nsrc/python/fixed.py|R1|finding that no longer exists\n")
    stale = _lint_fixture("r1", "R1", baseline_path=str(baseline))
    assert stale.new == []
    assert stale.stale == [
        "src/python/fixed.py|R1|finding that no longer exists"]


def test_baseline_matching_is_multiset():
    result = _lint_fixture("r1", "R1")
    one_entry = [result.new[0].fingerprint]
    # duplicate findings need duplicate entries: one entry absorbs one
    new, grandfathered, stale = apply_baseline(result.new, one_entry)
    assert len(grandfathered) == 1 and len(new) == 2 and not stale


# -- the per-file ModuleInfo cache -------------------------------------------


def test_module_cache_cold_then_warm():
    """lint_paths memoizes per-file analysis by (path, mtime, size):
    the second identical run re-parses NOTHING — the property that
    keeps tools/check.py and the tier-1 lint tests roughly flat
    despite the interprocedural pass re-linting the tree."""
    from tpulint import CACHE_STATS, clear_module_cache

    clear_module_cache()
    target = os.path.join(FIXTURES, "r1")
    lint_paths([target], rules=["R1"], repo_root=REPO_ROOT)
    cold = dict(CACHE_STATS)
    assert cold["misses"] > 0 and cold["hits"] == 0
    lint_paths([target], rules=["R1"], repo_root=REPO_ROOT)
    warm = dict(CACHE_STATS)
    assert warm["misses"] == cold["misses"], "warm run re-parsed a file"
    assert warm["hits"] == cold["misses"]


def test_module_cache_invalidates_on_file_change(tmp_path):
    """A changed file (new mtime/size) re-analyzes — stale ModuleInfos
    must never outlive the bytes they describe."""
    from tpulint import clear_module_cache

    mod = tmp_path / "mod.py"
    mod.write_text(
        "import threading\nimport time\n"
        "_lock = threading.Lock()\n\n\n"
        "def f():\n    with _lock:\n        time.sleep(1)\n")
    clear_module_cache()
    first = lint_paths([str(mod)], rules=["R2"], repo_root=str(tmp_path))
    assert len(first.new) == 1
    mod.write_text(
        "import threading\nimport time\n"
        "_lock = threading.Lock()\n\n\n"
        "def f():\n    with _lock:\n        pass\n    time.sleep(1)\n")
    os.utime(mod, ns=(0, 0))  # distinct stamp even on a fast rewrite
    second = lint_paths([str(mod)], rules=["R2"], repo_root=str(tmp_path))
    assert second.new == []
    clear_module_cache()


# -- the tier-1 environmental-noise ratchet ----------------------------------


SNAPSHOT = os.path.join(TOOLS, "t1_noise_snapshot.txt")


def test_noise_snapshot_is_the_known_environmental_set():
    """The checked-in snapshot holds exactly the ROADMAP's 9F+7E
    (cc_tls openssl, llama sharding, tp_served numerics) — growing it
    needs the same justification as a baseline entry."""
    sys.path.insert(0, TOOLS)
    try:
        import t1_noise
    finally:
        sys.path.remove(TOOLS)
    ids = t1_noise.load_snapshot(SNAPSHOT)
    assert len(ids) == 16
    by_file = {}
    for nodeid in ids:
        by_file.setdefault(nodeid.split("::")[0], []).append(nodeid)
    assert sorted(by_file) == [
        "tests/test_cc_tls.py", "tests/test_llama.py",
        "tests/test_tp_served_server.py"]
    assert len(by_file["tests/test_cc_tls.py"]) == 8
    assert len(by_file["tests/test_llama.py"]) == 6
    assert len(by_file["tests/test_tp_served_server.py"]) == 2


def test_noise_ratchet_fails_only_when_the_set_grows(tmp_path):
    """The mechanized "don't let it grow" note: a new failure id exits
    1 naming it; a fixed one exits 0 with a ratchet-down notice; the
    identical set is quiet.  FAILED<->ERROR flips are not growth."""
    with open(SNAPSHOT, "r", encoding="utf-8") as fh:
        known = [ln for ln in fh.read().splitlines()
                 if ln and not ln.startswith("#")]
    log = tmp_path / "t1.log"

    def run(lines):
        log.write_text("\n".join(lines) + "\n")
        return _run([sys.executable, "tools/t1_noise.py", str(log)])

    same = run(["= short test summary info ="] + known)
    assert same.returncode == 0, same.stdout + same.stderr
    assert "no new tier-1 noise" in same.stdout

    grown = run(known + [
        "FAILED tests/test_new.py::test_regression - AssertionError: x"])
    assert grown.returncode == 1
    assert "tests/test_new.py::test_regression" in grown.stderr

    # a module-level collection error has no '::' — an entire broken
    # test module is growth too
    collect = run(known + [
        "ERROR tests/test_broken.py - ImportError: boom"])
    assert collect.returncode == 1
    assert "tests/test_broken.py" in collect.stderr

    fixed = run(known[1:])
    assert fixed.returncode == 0
    assert "ratchet down" in fixed.stdout

    flipped = run(["ERROR " + known[-1].split(None, 1)[1]]
                  + known[:-1])
    assert flipped.returncode == 0, flipped.stdout + flipped.stderr


# -- the CLI and the check.py wrapper ----------------------------------------


def _run(cmd):
    return subprocess.run(
        cmd, cwd=REPO_ROOT, capture_output=True, text=True, timeout=120)


def test_cli_clean_tree_exits_zero():
    proc = _run([sys.executable, "tools/tpulint.py"])
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_cli_findings_exit_nonzero_and_render_file_line():
    proc = _run([
        sys.executable, "tools/tpulint.py", "--rules", "R2",
        "--baseline", "", "--docs", "",
        os.path.join("tests", "tpulint_fixtures", "r2")])
    assert proc.returncode == 1
    assert "r2/bad.py:14 R2(no-blocking-under-lock)" in proc.stdout.replace(
        os.sep, "/")


def test_cli_explain():
    proc = _run([sys.executable, "tools/tpulint.py", "--explain", "R3"])
    assert proc.returncode == 0
    assert "monotonic" in proc.stdout
    proc = _run([sys.executable, "tools/tpulint.py", "--explain", "R9"])
    assert proc.returncode == 2


def test_check_py_wrapper_is_clean():
    """The one-command lint gate (tpulint + optional ruff) passes on
    the tree — its default scope is src/python AND tools; a missing
    ruff binary is a skip, never a failure.  (--no-t1 keeps the
    verdict hermetic: it must not depend on whatever tier-1 log an
    earlier run left in /tmp.)"""
    proc = _run([sys.executable, "tools/check.py", "--no-t1"])
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "clean" in proc.stdout


def test_check_py_changed_only_mode():
    """--changed-only (the pre-commit loop) exits clean on the repo:
    either no lintable diffs from merge-base, or the changed files
    lint clean — and a broken git never breaks the gate (full-tree
    fallback, exercised via a bogus GIT_DIR)."""
    proc = _run([sys.executable, "tools/check.py", "--changed-only",
                 "--no-ruff", "--no-t1"])
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "clean" in proc.stdout
    env = dict(os.environ, GIT_DIR=os.path.join(REPO_ROOT, "nonexistent"))
    proc = subprocess.run(
        [sys.executable, "tools/check.py", "--changed-only", "--no-ruff",
         "--no-t1"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=120,
        env=env)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "full tree" in proc.stderr


def test_check_py_t1_noise_ratchet_wiring(tmp_path):
    """check.py folds the tier-1 noise ratchet in exactly when a
    COMPLETED tier-1 log is named: new failures beyond the snapshot
    fail the check, a log with no pytest summary (a run still in
    flight — check.py runs inside that suite) is skipped, and naming a
    missing log explicitly is an error."""
    base = [sys.executable, "tools/check.py", "--no-ruff"]
    # a completed log with a failure the snapshot does not grandfather
    bad = tmp_path / "t1_bad.log"
    bad.write_text("FAILED tests/test_x.py::test_new - boom\n"
                   "1 failed, 2 passed in 3.21s\n")
    proc = _run(base + ["--t1-log", str(bad)])
    assert proc.returncode == 1
    assert "NEW tier-1 failure" in proc.stdout + proc.stderr
    # the same failure in a log WITHOUT a summary line: run in flight,
    # ratchet skipped, gate clean
    partial = tmp_path / "t1_partial.log"
    partial.write_text("FAILED tests/test_x.py::test_new - boom\n")
    proc = _run(base + ["--t1-log", str(partial)])
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "no pytest summary" in proc.stderr
    # explicitly naming a log that does not exist is an error ...
    proc = _run(base + ["--t1-log", str(tmp_path / "nope.log")])
    assert proc.returncode == 1
    # ... as is the flag with no value (typed, not a traceback)
    proc = _run(base + ["--t1-log"])
    assert proc.returncode == 2
    assert "needs a path" in proc.stderr
    # ... but --no-t1 bypasses the ratchet entirely
    proc = _run(base + ["--no-t1", "--t1-log", str(bad)])
    assert proc.returncode == 0, proc.stdout + proc.stderr


# -- layer 3: doc drift ------------------------------------------------------


def _resilience_text():
    with open(RESILIENCE_MD, "r", encoding="utf-8") as fh:
        return fh.read()


def _doc_section(text, title):
    """One `## title` section of a markdown doc (to its next `## `)."""
    marker = "\n## {}\n".format(title)
    start = text.index(marker)
    end = text.find("\n## ", start + len(marker))
    return text[start:end if end != -1 else len(text)]


def test_fault_table_matches_points_registry():
    """docs/resilience.md's fault-injection table documents exactly the
    points registered in faults.POINTS (R6 pins code<->registry; this
    pins registry<->docs)."""
    import re

    from tpuserver import faults

    text = _doc_section(_resilience_text(), "Fault injection")
    documented = set(re.findall(r"^\|\s*`([a-z_.]+)`\s*\|", text,
                                flags=re.MULTILINE))
    assert documented == set(faults.POINTS), (
        "fault table drift: documented-only={}, registry-only={}".format(
            documented - set(faults.POINTS),
            set(faults.POINTS) - documented))


def test_chaos_campaign_tables_match_chaoslib():
    """docs/resilience.md's "Chaos campaigns" tables document exactly
    chaoslib's surfaces: the fault-kind rows are FAULT_KINDS (with the
    right serial-group column) and the invariant rows are the named
    checks the module docstring catalogs — doc, registry, and library
    cannot drift apart."""
    import re

    from tpuserver import chaoslib

    section = _doc_section(_resilience_text(), "Chaos campaigns")
    rows = re.findall(r"^\|\s*`([a-z_.]+)`\s*\|\s*([^|]*)\|", section,
                      flags=re.MULTILINE)
    documented = {name for name, _ in rows}
    kinds = set(chaoslib.FAULT_KINDS)
    invariants = set(re.findall(r"^``([a-z_]+)``\s", chaoslib.__doc__,
                                flags=re.MULTILINE))
    assert invariants, "chaoslib docstring catalog unparseable"
    assert documented == kinds | invariants, (
        "chaos-campaign table drift: documented-only={}, "
        "library-only={}".format(documented - (kinds | invariants),
                                 (kinds | invariants) - documented))
    for name, group_cell in rows:
        if name not in kinds:
            continue
        group = chaoslib.FAULT_KINDS[name][1]
        expect = "`{}`".format(group) if group else "—"
        assert expect in group_cell, (
            "fault kind {} documents serial group {!r}, registry says "
            "{!r}".format(name, group_cell.strip(), group))


def test_scheduler_stats_keys_are_documented():
    """Every counter DecodeScheduler.stats() returns is named (as
    `backticked` code) in docs/resilience.md — ops docs cannot drift
    from the introspection surface."""
    from tpuserver.scheduler import DecodeScheduler

    # stats() touches no device state: fns/params may be None
    sched = DecodeScheduler(None, None, max_slots=1, max_seq=8)
    try:
        keys = set(sched.stats())
    finally:
        sched.close(join_timeout=0.1)
    text = _resilience_text()
    missing = {k for k in keys if "`{}`".format(k) not in text}
    assert not missing, (
        "DecodeScheduler.stats() keys undocumented in "
        "docs/resilience.md: {}".format(sorted(missing)))


def test_decode_loop_has_one_step_dispatch():
    """``DecodeScheduler._loop`` has ONE step body: it subscripts
    ``fns["step"]`` once, to put it behind ``_ControlledStep`` (which
    calls it at one site), dispatches that at exactly one call site, and
    names no second step executable (whoever brings multi-token
    verification back writes it against this body, not beside it)."""
    import ast
    import inspect
    import textwrap

    from tpuserver.scheduler import DecodeScheduler, _ControlledStep

    fn = ast.parse(textwrap.dedent(
        inspect.getsource(DecodeScheduler._loop))).body[0]
    keys = [node.slice.value for node in ast.walk(fn)
            if isinstance(node, ast.Subscript)
            and isinstance(node.value, ast.Name) and node.value.id == "fns"
            and isinstance(node.slice, ast.Constant)]
    calls = [node.func.slice.value for node in ast.walk(fn)
             if isinstance(node, ast.Call)
             and isinstance(node.func, ast.Subscript)
             and isinstance(node.func.value, ast.Name)
             and node.func.value.id == "fns"]
    dispatches = [node for node in ast.walk(fn)
                  if isinstance(node, ast.Call)
                  and isinstance(node.func, ast.Name)
                  and node.func.id == "controlled"]
    body = ast.parse(textwrap.dedent(
        inspect.getsource(_ControlledStep._control_step))).body[0]
    steps = [node for node in ast.walk(body) if isinstance(node, ast.Call)
             and isinstance(node.func, ast.Attribute)
             and node.func.attr == "_step"]
    assert keys.count("step") == 1 and "step" not in calls
    assert len(dispatches) == 1 and len(steps) == 1
    # the other executables of the bundle admit, prefill and park
    assert {k for k in keys if "step" in k} == {"step"}, keys


OBSERVABILITY_MD = os.path.join(REPO_ROOT, "docs", "observability.md")


def test_metric_catalog_matches_observability_doc():
    """docs/observability.md's metric catalog documents exactly the
    families declared in tpuserver.metrics.CATALOG — the faults.POINTS
    code<->registry<->docs triangle, applied to the telemetry plane
    (the registry itself enforces code<->CATALOG; this pins
    CATALOG<->docs)."""
    import re

    from tpuserver import metrics as tmetrics

    with open(OBSERVABILITY_MD, "r", encoding="utf-8") as fh:
        text = fh.read()
    documented = set(re.findall(r"`(tpu_[a-z0-9_]+)`", text))
    assert documented == set(tmetrics.CATALOG), (
        "metric catalog drift: documented-only={}, registry-only={}"
        .format(documented - set(tmetrics.CATALOG),
                set(tmetrics.CATALOG) - documented))


def test_metric_catalog_is_well_formed():
    """Every CATALOG entry carries a valid type and a help string, and
    counters follow the Prometheus ``*_total`` naming convention."""
    from tpuserver import metrics as tmetrics

    for name, (kind, help_text) in tmetrics.CATALOG.items():
        assert kind in ("counter", "gauge", "histogram"), name
        assert isinstance(help_text, str) and help_text, name
        if kind == "counter":
            assert name.endswith("_total"), (
                "counter '{}' must end in _total".format(name))
    # the registry refuses names outside the catalog (the code<->
    # CATALOG leg of the triangle is enforcement, not convention)
    registry = tmetrics.MetricsRegistry()
    with pytest.raises(ValueError):
        registry.counter("tpu_not_in_catalog_total")


def test_points_registry_is_importable_and_described():
    from tpuserver import faults

    assert set(faults.POINTS) == {
        "scheduler.step", "scheduler.fetch", "scheduler.admit",
        "core.shm_read", "http.generate_stream", "grpc.stream_infer",
    }
    assert all(isinstance(v, str) and v for v in faults.POINTS.values())


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-v"]))
