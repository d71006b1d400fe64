"""dslint (deepspeed_tpu.analysis) tests.

Golden contract: every fixture under tests/fixtures/dslint/ plants its
violations on lines marked ``# PLANT:`` — a rule passes when the set of
flagged lines EQUALS the set of planted lines in its bad fixture (no
misses, no extras) and it stays silent on the paired near-miss clean
fixture. Plus: suppression parsing, baseline add/remove round-trip, the
repo-wide gate invariant (zero unsuppressed findings on the shipped
package), and traced-set spot checks against the real codebase.
"""

import json
import os

import pytest

from deepspeed_tpu.analysis import (Baseline, all_rules, analyze,
                                    build_package_model, known_rule_ids,
                                    main)

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures", "dslint")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "deepspeed_tpu")


def fixture(name):
    return os.path.join(FIXTURES, name)


# whole-repo model builds and rule runs cost seconds each — the
# repo-wide assertions share ONE of each (tier-1 budget discipline)
@pytest.fixture(scope="module")
def repo_pkg():
    return build_package_model([PKG], base=REPO)


@pytest.fixture(scope="module")
def repo_findings():
    return analyze([PKG], base=REPO)


def planted_lines(name):
    with open(fixture(name)) as fh:
        return {i for i, line in enumerate(fh, 1) if "PLANT:" in line}


def live(findings, rule=None):
    return [f for f in findings
            if not f.suppressed and not f.baselined
            and (rule is None or f.rule == rule)]


# -- rule catalog -------------------------------------------------------

def test_rule_catalog():
    rules = all_rules()
    assert set(rules) == {"host-sync", "trace-hygiene",
                          "recompile-hazard", "lock-discipline",
                          "exception-discipline", "wall-clock",
                          "comm-facade", "races"}
    assert "suppression" in known_rule_ids()
    for cls in rules.values():
        assert cls.summary


# -- golden: every rule catches its plants, misses its near-misses ------

@pytest.mark.parametrize("rule,bad,ok", [
    ("host-sync", "host_sync_bad.py", "host_sync_ok.py"),
    ("trace-hygiene", "trace_hygiene_bad.py", "trace_hygiene_ok.py"),
    ("recompile-hazard", "recompile_bad.py", "recompile_ok.py"),
    ("lock-discipline", "locks_bad.py", "locks_ok.py"),
    # region/cell tier of the documented lock order (region -> cell ->
    # fleet -> replica): a cell-acquires-region and a fleet-acquires-
    # cell inversion, with the descending near-misses in the ok twin
    ("lock-discipline", os.path.join("serving", "locks_bad.py"),
     os.path.join("serving", "locks_ok.py")),
    ("exception-discipline", "exceptions_bad.py", "exceptions_ok.py"),
    # wall-clock fixtures sit under a serving/ subdir: the rule is
    # scoped to the clocked layers by module path
    ("wall-clock", os.path.join("serving", "wall_clock_bad.py"),
     os.path.join("serving", "wall_clock_ok.py")),
    # comm-facade fixtures sit under a parallel/ subdir named zero_*.py:
    # the rule is scoped to the ZeRO-3 hot-path modules by file path
    ("comm-facade", os.path.join("parallel", "zero_bad.py"),
     os.path.join("parallel", "zero_ok.py")),
    # dsrace lockset analysis: a worker thread + public surface racing
    # on shared attributes; the ok twin exercises every safe idiom
    # (one lock, entry-lockset inference, queue hand-off, one-shot
    # latch, init publish)
    ("races", os.path.join("serving", "races_bad.py"),
     os.path.join("serving", "races_ok.py")),
])
def test_rule_golden(rule, bad, ok):
    bad_found = live(analyze([fixture(bad)]), rule)
    assert bad_found, f"{rule} found nothing in {bad}"
    assert {f.line for f in bad_found} == planted_lines(bad), (
        f"{rule} flagged lines != planted lines in {bad}:\n" +
        "\n".join(f"  {f.line}: [{f.code}] {f.message}"
                  for f in bad_found))
    ok_found = live(analyze([fixture(ok)]), rule)
    assert not ok_found, (
        f"{rule} false-positives in {ok}:\n" +
        "\n".join(f"  {f.line}: [{f.code}] {f.message}"
                  for f in ok_found))


def test_host_sync_subchecks_all_fire():
    codes = {f.code for f in live(analyze([fixture("host_sync_bad.py")]),
                                  "host-sync")}
    assert {"item-call", "scalar-cast", "print", "np-convert",
            "block_until_ready-call"} <= codes


def test_trace_hygiene_subchecks_all_fire():
    codes = {f.code
             for f in live(analyze([fixture("trace_hygiene_bad.py")]),
                           "trace-hygiene")}
    assert {"global-stmt", "wall-clock", "np-random", "attr-mutation",
            "telemetry-call", "tracer-call"} <= codes


def test_recompile_subchecks_all_fire():
    codes = {f.code
             for f in live(analyze([fixture("recompile_bad.py")]),
                           "recompile-hazard")}
    assert {"jit-in-loop", "jit-per-call", "unhashable-static",
            "varying-static"} <= codes


def test_lock_subchecks_all_fire():
    codes = {f.code for f in live(analyze([fixture("locks_bad.py")]),
                                  "lock-discipline")}
    assert {"blocking-under-lock", "callback-under-lock",
            "order-violation", "lock-cycle", "self-deadlock"} <= codes


def test_wall_clock_subchecks_all_fire():
    codes = {f.code
             for f in live(analyze([fixture(os.path.join(
                 "serving", "wall_clock_bad.py"))]), "wall-clock")}
    assert {"direct-time", "raw-event-wait"} == codes


def test_comm_facade_subchecks_fire_on_every_import_flavor():
    found = live(analyze([fixture(os.path.join("parallel", "zero_bad.py"))]),
                 "comm-facade")
    assert {f.code for f in found} == {"raw-collective"}
    # every import flavor resolves: jax.lax.X, lax alias, import-as,
    # from-imported name, and collectives inside nested closures
    assert len(found) == 6
    flagged = {f.message.split("raw jax.lax.")[1].split(" ")[0]
               for f in found}
    assert {"psum", "pmean", "psum_scatter", "all_gather", "all_to_all",
            "ppermute"} == flagged


def test_comm_facade_out_of_scope_module_is_ignored():
    # the same raw collectives OUTSIDE parallel/zero*.py / runtime/
    # engine*.py are not this rule's business (ring/ulysses/compressed
    # are the low-level implementation layer the facade wraps)
    found = live(analyze([fixture("host_sync_bad.py")]), "comm-facade")
    assert found == []


def test_comm_facade_repo_hot_paths_clean():
    # the shipped ZeRO-3 hot paths route every collective through the
    # facade: the repo gate invariant this rule exists to keep
    found = live(analyze([os.path.join(PKG, "parallel", "zero.py"),
                          os.path.join(PKG, "runtime", "engine.py")]),
                 "comm-facade")
    assert found == []


def test_wall_clock_out_of_scope_module_is_ignored():
    # the same violations OUTSIDE serving//resilience//telemetry/ are
    # not this rule's business (the engine's host-overhead ledger etc.
    # legitimately reads wall time)
    found = live(analyze([fixture("host_sync_bad.py")]), "wall-clock")
    assert found == []


def test_races_subchecks_all_fire():
    codes = {f.code
             for f in live(analyze([fixture(os.path.join(
                 "serving", "races_bad.py"))]), "races")}
    assert {"write-write", "read-write"} == codes


def test_exception_subchecks_all_fire():
    codes = {f.code
             for f in live(analyze([fixture("exceptions_bad.py")]),
                           "exception-discipline")}
    assert {"broad-except", "bare-except", "broad-baseexception",
            "caught-injected-fault"} == codes


# -- suppressions -------------------------------------------------------

def test_suppression_parsing():
    fs = analyze([fixture("suppressions_fixture.py")])
    by_symbol = {}
    for f in fs:
        by_symbol.setdefault(f.symbol, []).append(f)

    [ok] = [f for f in by_symbol["suppressed_ok"] if f.rule == "host-sync"]
    assert ok.suppressed
    [nl] = [f for f in by_symbol["next_line_form"]
            if f.rule == "host-sync"]
    assert nl.suppressed

    # a reasonless suppression suppresses nothing and is itself flagged
    [rless] = [f for f in by_symbol["reasonless"]
               if f.rule == "host-sync"]
    assert not rless.suppressed
    assert any(f.rule == "suppression" and f.code == "missing-reason"
               for f in fs)

    # unknown rule id: flagged, and the print stays live
    [unk] = [f for f in by_symbol["unknown_rule"]
             if f.rule == "host-sync"]
    assert not unk.suppressed
    assert any(f.rule == "suppression" and f.code == "unknown-rule"
               for f in fs)

    # a suppression matching nothing is reported as unused
    assert any(f.rule == "suppression" and f.code == "unused"
               and f.line in planted_unused_line()
               for f in fs)

    # one comment can suppress multiple families on its line
    multi = [f for f in by_symbol["multi_rule"]
             if f.rule in ("host-sync", "trace-hygiene")]
    assert {f.rule for f in multi} == {"host-sync", "trace-hygiene"}
    assert all(f.suppressed for f in multi)
    # ...but accounting is per RULE: a listed family that never fires on
    # the line is reported unused even though the other one matched
    [partial] = [f for f in by_symbol["multi_rule_partial"]
                 if f.rule == "host-sync"]
    assert partial.suppressed
    partial_line = partial.line
    assert any(f.rule == "suppression" and f.code == "unused"
               and f.line == partial_line
               and "trace-hygiene" in f.message
               for f in fs)


def planted_unused_line():
    with open(fixture("suppressions_fixture.py")) as fh:
        return {i for i, line in enumerate(fh, 1)
                if "nothing on this line fires" in line}


# -- baseline round-trip ------------------------------------------------

def test_baseline_roundtrip(tmp_path):
    fs = analyze([fixture("host_sync_bad.py")])
    assert live(fs)
    path = str(tmp_path / "baseline.json")

    # add: everything live today is grandfathered
    Baseline.from_findings(fs).save(path)
    fs2 = analyze([fixture("host_sync_bad.py")])
    stale = Baseline.load(path).absorb(fs2)
    assert stale == 0
    assert not live(fs2), "baselined findings must not be live"
    assert all(f.baselined for f in fs2 if not f.suppressed)

    # remove: fixing a finding leaves a stale entry the tool reports
    data = json.loads(open(path).read())
    dropped = data["entries"].pop()
    open(path, "w").write(json.dumps(data))
    fs3 = analyze([fixture("host_sync_bad.py")])
    stale3 = Baseline.load(path).absorb(fs3)
    assert stale3 == 0   # entries removed, finding now LIVE, none stale
    assert len(live(fs3)) == dropped["count"]

    # stale direction: baseline mentions a finding the code no longer has
    Baseline.from_findings(fs).save(path)
    fs_ok = analyze([fixture("host_sync_ok.py")])
    stale_ok = Baseline.load(path).absorb(fs_ok)
    assert stale_ok == len(json.loads(open(path).read())["entries"])


def test_fingerprints_survive_line_drift():
    fs = analyze([fixture("host_sync_bad.py")])
    f = live(fs)[0]
    fp = f.fingerprint()
    f.line += 40          # same code on a different line
    assert f.fingerprint() == fp
    f.source_line = "something_else()"
    assert f.fingerprint() != fp


# -- the repo gate ------------------------------------------------------

def test_repo_package_is_clean_under_committed_baseline(repo_findings):
    """The CI gate invariant: zero unsuppressed, un-baselined findings
    on the shipped package, and no stale baseline entries."""
    fs = repo_findings
    stale = Baseline.load(os.path.join(REPO,
                                       "dslint_baseline.json")).absorb(fs)
    problems = live(fs)
    assert not problems, (
        "dslint gate would fail:\n" +
        "\n".join(f"  {f.location()}: {f.rule}[{f.code}] {f.message}"
                  for f in problems))
    assert stale == 0, "stale dslint_baseline.json entries — " \
                       "run --update-baseline"


def test_every_shipped_suppression_has_a_reason(repo_findings):
    # reasonless suppressions surface as findings; the gate test above
    # would catch them — this asserts the stronger property directly
    assert not [f for f in repo_findings if f.rule == "suppression"]


# -- traced-set spot checks against the real codebase -------------------

def test_traced_set_on_real_engine(repo_pkg):
    pkg = repo_pkg
    traced = {k for k, f in pkg.functions.items()
              if f.traced_reason is not None}

    def find(substr):
        return [k for k in pkg.functions if substr in k]

    # the fused train-step scan body is traced
    assert any("train_step" in k for k in traced)
    # the serving driver tick is host code — must NOT be traced
    for k in find("ServingEngine._tick"):
        assert k not in traced
    # locks were modeled for the serving classes
    se = [c for c in pkg.classes.values() if c.name == "ServingEngine"]
    assert se and "_lock" in se[0].lock_attrs


def test_lock_graph_documented_order_holds_in_repo(repo_findings):
    """No replica->fleet edge and no cycle exists in the shipped code —
    the discipline docs/serving.md documents, now machine-checked."""
    assert not [f for f in repo_findings
                if f.rule == "lock-discipline"
                and f.code in ("order-violation", "lock-cycle")
                and not f.suppressed and not f.baselined]


# -- thread model + weak-resolution spot checks (dsrace, PR 15) ---------

def test_thread_model_discovers_serving_entry_points(repo_pkg):
    pkg = repo_pkg
    by_role = {e.role: e.func_key for e in pkg.thread_entries}
    assert by_role["serving-driver"].endswith("ServingEngine._drive")
    assert by_role["serving-watchdog"].endswith("ServingEngine._watch")
    assert by_role["fleet-monitor"].endswith("ServingFleet._monitor_loop")
    assert by_role["region-monitor"].endswith("Region._monitor_loop")
    assert "finalizer" in by_role        # dataloader weakref.finalize

    def roles_of(suffix):
        [f] = [f for k, f in pkg.functions.items() if k.endswith(suffix)]
        return f.thread_roles

    # the driver loop runs ONLY on its thread; the tick body runs on
    # the driver AND via the public step() seam (caller threads)
    assert roles_of("ServingEngine._drive") == {"serving-driver"}
    assert {"serving-driver", "main"} <= roles_of("ServingEngine._tick")
    # roles propagate through the call graph into shared helpers
    assert {"serving-driver", "main"} <= roles_of("ServingEngine._retire")


def test_weak_resolution_blocklist_covers_new_method_names(repo_pkg):
    """PR-15 refresh: `step`/`route`/`adopt`/`evacuate`/`publish` are
    common serving-tier verbs — a weak (unique-bare-name) resolution of
    any of them would hijack unrelated call sites. Pinned both in the
    blocklist constant and as a behavioral property of the built
    model: no weak edge ever targets a blocklisted name."""
    from deepspeed_tpu.analysis.model import _WEAK_RESOLVE_BLOCKLIST

    assert {"step", "route", "adopt", "evacuate",
            "publish"} <= _WEAK_RESOLVE_BLOCKLIST
    pkg = repo_pkg
    for f in pkg.functions.values():
        for site in f.calls:
            if site.weak:
                for t in site.targets:
                    assert pkg.functions[t].name \
                        not in _WEAK_RESOLVE_BLOCKLIST, (
                            f"weak edge {f.key} -> {t} resolves a "
                            f"blocklisted name")


def test_static_lock_graph_sees_property_edges(repo_pkg):
    """The cross-validation contract's static half: the fleet's gauge
    pass acquires replica locks through @property reads, and the
    region's route path acquires cell locks through the digest
    property — both edges must exist in the static lock graph, or the
    runtime sanitizer's observations would (rightly) fail the lane."""
    from deepspeed_tpu.analysis.rules.locks import collect_lock_graph

    graph = collect_lock_graph(repo_pkg)
    assert ("ServingFleet._lock", "ServingEngine._lock") in graph
    assert ("Region._lock", "ServingCell._lock") in graph


def test_weak_resolution_skips_external_call_results(repo_pkg):
    """``hashlib.sha256(data).digest()`` in router._hash64 is a method
    on an EXTERNAL object; weak-resolving it to the one package method
    named ``digest`` (ServingCell.digest) planted a phantom
    Fleet->Cell edge no runtime path can exercise — which failed the
    race lane's hot-edge coverage gate. The resolver must leave calls
    on unresolvable-call results untargeted."""
    from deepspeed_tpu.analysis.rules.locks import collect_lock_graph

    graph = collect_lock_graph(repo_pkg)
    assert ("ServingFleet._lock", "ServingCell._lock") not in graph
    # the REAL Region->Cell path (typed cell receiver) must survive the
    # narrowing — only the external-receiver guess goes away
    assert ("Region._lock", "ServingCell._lock") in graph


def test_locksan_seam_keeps_lock_model_intact(repo_pkg):
    """Serving locks are built through resilience/locksan.named_rlock;
    the static model must keep seeing them as RLock attributes (the
    whole lock-discipline + races machinery keys off lock_attrs)."""
    for cls_name in ("ServingEngine", "ServingFleet", "ServingCell",
                     "Region"):
        [c] = [c for c in repo_pkg.classes.values()
               if c.name == cls_name]
        assert c.lock_attrs.get("_lock") == "RLock", cls_name


def test_races_rule_fixed_sites_stay_clean(repo_findings):
    """Regression pins for the PR-15 triage fixes: the attributes whose
    races were FIXED (not suppressed) must not re-fire — a revert of
    any fix shows up here by name, not just as a gate count."""
    fs = repo_findings
    fixed_attrs = {"_last_autoscale", "_pending_engine",
                   "_partition_epoch_seen", "_partition_active",
                   "route_work_last", "_spec_ema_by_class",
                   "_last_gauges", "_remaining", "_partitions"}
    hits = [f for f in fs if f.rule == "races"
            and any(f".{a}:" in f.message for a in fixed_attrs)]
    assert not hits, "\n".join(f"  {f.location()}: {f.message}"
                               for f in hits)


# -- CLI ----------------------------------------------------------------

def test_cli_json_and_check_exit_codes(tmp_path, capsys):
    rc = main([fixture("host_sync_bad.py"), "--format", "json",
               "--check"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert out["summary"]["live"] > 0
    assert all("fingerprint" in f for f in out["findings"])

    rc = main([fixture("host_sync_ok.py"), "--check"])
    capsys.readouterr()
    assert rc == 0

    # baseline workflow through the CLI: update, then check passes
    bl = str(tmp_path / "bl.json")
    rc = main([fixture("host_sync_bad.py"), "--baseline", bl,
               "--update-baseline"])
    assert rc == 0
    rc = main([fixture("host_sync_bad.py"), "--baseline", bl, "--check"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "gate: PASS" in out


def test_cli_list_rules(capsys):
    assert main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rid in ("host-sync", "trace-hygiene", "recompile-hazard",
                "lock-discipline", "exception-discipline", "races",
                "suppression"):
        assert rid in out


def test_cli_changed_mode(tmp_path, capsys, monkeypatch):
    """--changed analyzes only files changed vs HEAD (the pre-commit
    fast mode) and stays quiet about cross-module 'unused suppression'
    verdicts a scoped model cannot judge."""
    import shutil
    import subprocess

    repo = tmp_path / "r"
    repo.mkdir()

    def git(*args):
        subprocess.run(["git", *args], cwd=repo, check=True,
                       capture_output=True,
                       env={**os.environ,
                            "GIT_AUTHOR_NAME": "t",
                            "GIT_AUTHOR_EMAIL": "t@t",
                            "GIT_COMMITTER_NAME": "t",
                            "GIT_COMMITTER_EMAIL": "t@t"})

    git("init", "-q")
    (repo / "clean.py").write_text("x = 1\n")
    git("add", "clean.py")
    git("commit", "-qm", "seed")
    monkeypatch.chdir(repo)

    # nothing changed: trivially green
    assert main(["--changed", "--check"]) == 0
    assert "no changed python files" in capsys.readouterr().out

    # an UNTRACKED file with a planted finding fails the changed gate
    shutil.copy(fixture("host_sync_bad.py"), repo / "bad.py")
    assert main(["--changed", "--check"]) == 1
    out = capsys.readouterr().out
    assert "bad.py" in out and "clean.py" not in out
    (repo / "bad.py").unlink()

    # a MODIFIED tracked file is picked up too: plant a finding into
    # the tracked file and the gate must flip to FAIL
    bad_src = open(fixture("host_sync_bad.py")).read()
    (repo / "clean.py").write_text(bad_src)
    assert main(["--changed", "--check"]) == 1
    assert "clean.py" in capsys.readouterr().out

    # ...and from a SUBDIRECTORY: git paths are repo-root relative, so
    # --changed must still see the change (regression: joining them
    # against the cwd dropped every file outside the subdir and
    # green-lit the gate)
    sub = repo / "pkg"
    sub.mkdir()
    monkeypatch.chdir(sub)
    assert main(["--changed", "--check"]) == 1
    assert "clean.py" in capsys.readouterr().out
