"""graftlint tests: fixture corpus expectations, suppression grammar,
baseline matching, the JSON envelope, exit codes, and the repo-wide
zero-unsuppressed gate.

The fixture corpus under ``tests/lint_fixtures/`` is the rule-level
contract: every ``# expect: GLxxx`` trailer must produce exactly that
active finding on that line, every ``# graftlint: disable=`` must
suppress one, and the clean sections must stay clean — so each rule is
pinned by at least one true positive, one suppressed finding, and one
allowlisted negative.
"""

import json
import os
import re
import subprocess
import sys
import textwrap

import pytest

from mingpt_distributed_tpu.analysis import Config, Engine, all_rules
from mingpt_distributed_tpu.analysis.cli import main as lint_main
from mingpt_distributed_tpu.analysis.core import Baseline

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tests", "lint_fixtures")
FIXTURE_FILES = sorted(
    f for f in os.listdir(FIXTURES) if f.endswith(".py"))

#: fixture scopes — the corpus lives under tests/, not the production
#: tree, so the path-scoped rules are re-pointed at it
FIXTURE_CONFIG = Config(
    clock_paths=("lint_fixtures/",),
    print_paths=("lint_fixtures/",),
    print_exempt_paths=(),
)

_EXPECT_RE = re.compile(r"expect:\s*(GL\d{3})")


def run_lint(paths, config=FIXTURE_CONFIG, **kwargs):
    return Engine(config=config, root=REPO, **kwargs).run(paths)


# ---------------------------------------------------------------------
# fixture corpus
# ---------------------------------------------------------------------


@pytest.mark.parametrize("name", FIXTURE_FILES)
def test_fixture_expectations(name):
    """Marked lines fire, unmarked lines don't — positives and
    allowlisted negatives in one assertion."""
    path = os.path.join(FIXTURES, name)
    with open(path, "r", encoding="utf-8") as fh:
        lines = fh.read().splitlines()
    expected = {}
    for i, text in enumerate(lines, start=1):
        ids = _EXPECT_RE.findall(text)
        if ids:
            expected[i] = set(ids)
    assert expected, f"{name} has no expect: markers"

    res = run_lint([path])
    assert not res.parse_errors
    got = {}
    for f in res.active:
        got.setdefault(f.line, set()).add(f.rule_id)
    assert got == expected


@pytest.mark.parametrize("name", FIXTURE_FILES)
def test_fixture_suppressions(name):
    """Every fixture exercises the inline-disable path at least once,
    and suppressed findings never count as active."""
    res = run_lint([os.path.join(FIXTURES, name)])
    assert res.suppressed_count >= 1
    assert all(not f.active for f in res.findings if f.suppressed)


def test_every_rule_has_a_firing_fixture():
    res = run_lint([os.path.join(FIXTURES, f) for f in FIXTURE_FILES])
    fired = {f.rule_id for f in res.active}
    fired |= {f.rule_id for f in res.findings if f.suppressed}
    all_ids = {cls.id for cls in all_rules()}
    assert fired == all_ids, f"rules with no fixture coverage: " \
                             f"{sorted(all_ids - fired)}"


# ---------------------------------------------------------------------
# suppression grammar
# ---------------------------------------------------------------------


def _write(tmp_path, body):
    p = tmp_path / "mod.py"
    p.write_text(textwrap.dedent(body))
    return str(p)


def test_disable_next_and_disable_file(tmp_path):
    path = _write(tmp_path, """\
        # graftlint: disable-file=GL003
        import jax

        @jax.jit
        def f(x):
            if x > 0:
                return str(x)
            # graftlint: disable-next=GL002
            y = str(x)
            return y, str(x)
        """)
    res = run_lint([path])
    # GL003 disabled for the whole file; one GL002 disabled by
    # disable-next; the other two GL002 (line 7 and line 10) are active
    assert {f.rule_id for f in res.active} == {"GL002"}
    assert len(res.active) == 2
    assert res.suppressed_count == 2


def test_disable_all_keyword(tmp_path):
    path = _write(tmp_path, """\
        import jax

        @jax.jit
        def f(x):
            return str(x)  # graftlint: disable=all
        """)
    res = run_lint([path])
    assert not res.active
    assert res.suppressed_count == 1


def test_multiline_statement_trailing_comment(tmp_path):
    """A disable comment on ANY physical line of the flagged statement
    counts — black puts trailing comments where it finds room."""
    path = _write(tmp_path, """\
        import jax

        @jax.jit
        def f(x, y):
            return str(
                x + y
            )  # graftlint: disable=GL002
        """)
    res = run_lint([path])
    assert not res.active
    assert res.suppressed_count == 1


# ---------------------------------------------------------------------
# baseline
# ---------------------------------------------------------------------


def _baseline_file(tmp_path, entries):
    p = tmp_path / "baseline.json"
    p.write_text(json.dumps(
        {"schema": "graftlint-baseline/1", "entries": entries}))
    return str(p)


def test_baseline_is_content_anchored(tmp_path):
    """Entries match on (rule, path suffix, line text) — edits above the
    grandfathered site must not invalidate the baseline."""
    body = """\
        import jax

        @jax.jit
        def f(x):
            return str(x)
        """
    path = _write(tmp_path, body)
    bl = Baseline.load(_baseline_file(tmp_path, [{
        "rule": "GL002", "path": "mod.py", "contains": "str(x)",
        "justification": "fixture"}]))
    res = Engine(config=FIXTURE_CONFIG, baseline=bl, root=REPO).run([path])
    assert not res.active and res.baselined_count == 1
    assert not res.stale_baseline

    # shift the finding down three lines: still baselined
    with open(path, "w", encoding="utf-8") as fh:
        fh.write("# one\n# two\n# three\n" + textwrap.dedent(body))
    res = Engine(config=FIXTURE_CONFIG, baseline=bl, root=REPO).run([path])
    assert not res.active and res.baselined_count == 1


def test_stale_baseline_entries_are_reported(tmp_path):
    path = _write(tmp_path, "x = 1\n")
    bl = Baseline.load(_baseline_file(tmp_path, [{
        "rule": "GL010", "path": "mod.py", "contains": "print(",
        "justification": "fixed long ago"}]))
    res = Engine(config=FIXTURE_CONFIG, baseline=bl, root=REPO).run([path])
    assert res.exit_code == 0
    assert [e.rule for e in res.stale_baseline] == ["GL010"]
    assert "stale baseline" in res.render_human()


def test_baseline_rejects_bad_schema(tmp_path):
    p = tmp_path / "bad.json"
    p.write_text(json.dumps({"schema": "nope/9", "entries": []}))
    with pytest.raises(ValueError):
        Baseline.load(str(p))


# ---------------------------------------------------------------------
# engine / CLI surface
# ---------------------------------------------------------------------


def test_exit_codes(tmp_path):
    clean = _write(tmp_path, "x = 1\n")
    assert run_lint([clean]).exit_code == 0
    dirty = str(tmp_path / "dirty.py")
    with open(dirty, "w", encoding="utf-8") as fh:
        fh.write("import jax\n\n@jax.jit\ndef f(x):\n    return str(x)\n")
    assert run_lint([dirty]).exit_code == 1
    broken = str(tmp_path / "broken.py")
    with open(broken, "w", encoding="utf-8") as fh:
        fh.write("def f(:\n")
    res = run_lint([broken])
    assert res.exit_code == 1 and res.parse_errors


def test_select_unknown_rule_is_usage_error():
    with pytest.raises(ValueError):
        Engine(select=["GL999"], root=REPO)
    assert lint_main(["--select", "GL999", "."]) == 2


def test_zero_module_clean_under_jit_hazard_rules():
    """ISSUE 9: parallel/zero.py's update-view transforms run inside the
    jitted train step, so the module must stay clean under the jit-hazard
    rules (GL001-GL006) outright — no suppressions, no baseline entries.
    The approved pattern (branching on frozen LeafPlan fields, which are
    python-static at trace time) is documented by the
    gl003_static_plan.py fixture."""
    path = os.path.join(
        REPO, "mingpt_distributed_tpu", "parallel", "zero.py")
    res = Engine(
        select=["GL001", "GL002", "GL003", "GL004", "GL005", "GL006"],
        root=REPO,
    ).run([path])
    assert not res.parse_errors
    assert res.findings == []  # not even suppressed or baselined ones


def test_tracing_modules_clean_under_clock_rule():
    """ISSUE 10: telemetry/tracing.py takes every timestamp from the
    caller (injected clock) and telemetry/flightrec.py's only wall read
    is the ``wall_ts`` epoch anchor on dumps — both are in GL007 scope
    (Config.clock_paths) and must stay clean outright, no suppressions.
    This pins the contract the chaos gate's exact-duration trace
    assertions rely on."""
    paths = [
        os.path.join(REPO, "mingpt_distributed_tpu", "telemetry", p)
        for p in ("tracing.py", "flightrec.py")
    ]
    cfg = Engine(select=["GL007"], root=REPO).config
    for p in paths:
        rel = os.path.relpath(p, REPO)
        assert cfg.clock_in_scope(rel), f"{rel} fell out of GL007 scope"
    res = Engine(select=["GL007"], root=REPO).run(paths)
    assert not res.parse_errors
    assert res.findings == []  # not even suppressed or baselined ones


def test_speculative_module_clean_under_recompile_and_clock_rules():
    """ISSUE 11: serving/speculative.py's verify body runs under ONE
    lifetime jit — a traced branch there (GL003) would retrace per
    acceptance pattern, and a wall-clock read (GL007, the module is in
    clock-discipline scope) would break the virtual-clock chaos tests
    that cover mid-burst deadlines. Both must hold outright — no
    suppressions, no baseline entries. The hazards and their approved
    host-side/masked idioms are pinned by the
    gl003_gl007_speculative.py fixture."""
    path = os.path.join(
        REPO, "mingpt_distributed_tpu", "serving", "speculative.py")
    cfg = Engine(select=["GL003", "GL007"], root=REPO).config
    rel = os.path.relpath(path, REPO)
    assert cfg.clock_in_scope(rel), f"{rel} fell out of GL007 scope"
    res = Engine(select=["GL003", "GL007"], root=REPO).run([path])
    assert not res.parse_errors
    assert res.findings == []  # not even suppressed or baselined ones


def test_sharded_engine_modules_clean_under_recompile_and_clock_rules():
    """ISSUE 14: serving/engine.py binds the pool's NamedSharding into
    each jit wrapper as a partial-bound constant (mesh-in-compile-key:
    one wrapper = one mesh = one executable per family) and keeps the
    single-device None branch in the un-jitted ``_pin_kv`` helper — a
    traced branch on the sharding (GL003) would specialise per value
    and break the one-executable guarantee the sharded selftest pins.
    engine.py and kv_pool.py are in GL007 scope (serving/) and must
    also stay wall-clock clean — placement must never buy timing
    nondeterminism. Both hold outright: no suppressions, no baseline
    entries. The hazard shapes and the approved partial-bound idiom are
    pinned by the gl003_gl007_sharded_engine.py fixture."""
    paths = [
        os.path.join(REPO, "mingpt_distributed_tpu", "serving", p)
        for p in ("engine.py", "kv_pool.py")
    ]
    cfg = Engine(select=["GL003", "GL007"], root=REPO).config
    for p in paths:
        rel = os.path.relpath(p, REPO)
        assert cfg.clock_in_scope(rel), f"{rel} fell out of GL007 scope"
    res = Engine(select=["GL003", "GL007"], root=REPO).run(paths)
    assert not res.parse_errors
    assert res.findings == []  # not even suppressed or baselined ones


def test_quant_module_clean_under_recompile_and_clock_rules():
    """ISSUE 18: serving/quant.py is consumed INSIDE the lifetime-jitted
    decode/prefill/verify bodies — a traced branch there (GL003) would
    specialise the families per quantization value and break the
    one-executable-per-family guarantee the quant selftest pins
    (compile_counts identical across kv_dtypes, zero recompiles). The
    module is in GL007 scope (serving/) and must also stay wall-clock
    clean — the quant-error gauge is sampled through the scheduler's
    injected clock. Both hold outright: no suppressions, no baseline
    entries. The hazard shapes (per-call descriptor branch, traced amax
    branch) and the approved idioms (partial-bound KVQuant, masked
    zero-channel select) are pinned by the gl003_gl007_quant.py
    fixture."""
    path = os.path.join(
        REPO, "mingpt_distributed_tpu", "serving", "quant.py")
    cfg = Engine(select=["GL003", "GL007"], root=REPO).config
    rel = os.path.relpath(path, REPO)
    assert cfg.clock_in_scope(rel), f"{rel} fell out of GL007 scope"
    res = Engine(select=["GL003", "GL007"], root=REPO).run([path])
    assert not res.parse_errors
    assert res.findings == []  # not even suppressed or baselined ones


def test_trafficlab_package_clean_under_clock_rule():
    """ISSUE 12: the traffic lab's byte-replayable sweeps depend on
    arrival schedules being virtual-timestamp data and the runner never
    reading a wall clock. The whole package is in GL007 scope
    (Config.clock_paths) and must be clock-clean outright — no
    suppressions, no baseline entries. The wall-clock shapes that would
    break replay are pinned by the gl007_trafficlab.py fixture."""
    pkg = os.path.join(REPO, "mingpt_distributed_tpu", "trafficlab")
    paths = sorted(
        os.path.join(pkg, f) for f in os.listdir(pkg) if f.endswith(".py"))
    assert len(paths) >= 5  # __init__, arrivals, policies, report, ...
    cfg = Engine(select=["GL007"], root=REPO).config
    for p in paths:
        rel = os.path.relpath(p, REPO)
        assert cfg.clock_in_scope(rel), f"{rel} fell out of GL007 scope"
    res = Engine(select=["GL007"], root=REPO).run(paths)
    assert not res.parse_errors
    assert res.findings == []  # not even suppressed or baselined ones


def test_procfleet_package_clean_under_clock_rule():
    """ISSUE 16: the procfleet chaos suite is sleep-free and
    byte-deterministic only because process-level faults (kill, hang,
    slow_socket) land as raised verdicts or clock skew on the injected
    clock — a wall sleep in the supervisor's respawn backoff or a
    ``time.monotonic()`` in an RPC deadline would silently turn the
    loopback chaos tests into wall-time tests. The whole package has an
    explicit GL007 scope entry (Config.clock_paths) and must be
    clock-clean outright — no suppressions, no baseline entries; socket
    timeouts stay allowed because they are connection attributes, not
    ``time.*`` calls. The hazard and approved shapes are pinned by the
    gl007_procfleet.py fixture."""
    pkg = os.path.join(
        REPO, "mingpt_distributed_tpu", "serving", "procfleet")
    paths = sorted(
        os.path.join(pkg, f) for f in os.listdir(pkg) if f.endswith(".py"))
    assert len(paths) >= 5  # __init__, rpc, transport, worker, supervisor
    cfg = Engine(select=["GL007"], root=REPO).config
    # pinned explicitly, not only via the serving/ prefix: narrowing
    # serving/ later must not silently drop procfleet from scope
    assert "serving/procfleet/" in cfg.clock_paths
    for p in paths:
        rel = os.path.relpath(p, REPO)
        assert cfg.clock_in_scope(rel), f"{rel} fell out of GL007 scope"
    res = Engine(select=["GL007"], root=REPO).run(paths)
    assert not res.parse_errors
    assert res.findings == []  # not even suppressed or baselined ones


def test_hostplane_module_clean_under_clock_rule():
    """ISSUE 19: the cross-host control plane is deterministic only
    because heartbeat deadlines, the token-bucket pacing budget, and
    transfer retries all live on the injected fleet clock — the module
    imports no ``time`` at all (pacing *advances* the clock; against a
    wall clock the caller injects ``sleep``). Pinned with its own
    explicit scope entry AND asserted clock-clean outright — no
    suppressions, no baseline entries. The hazard and approved shapes
    are pinned by the gl007_hostplane.py fixture."""
    path = os.path.join(REPO, "mingpt_distributed_tpu", "serving",
                        "procfleet", "hostplane.py")
    cfg = Engine(select=["GL007"], root=REPO).config
    assert "serving/procfleet/hostplane.py" in cfg.clock_paths
    rel = os.path.relpath(path, REPO)
    assert cfg.clock_in_scope(rel), f"{rel} fell out of GL007 scope"
    with open(path, "r", encoding="utf-8") as fh:
        source = fh.read()
    assert "import time" not in source  # stronger than lint: no module at all
    res = Engine(select=["GL007"], root=REPO).run([path])
    assert not res.parse_errors
    assert res.findings == []  # not even suppressed or baselined ones


def test_control_package_clean_under_clock_and_name_rules():
    """ISSUE 20: an autoscaled sweep is byte-replayable only because
    every governor decision is a function of ControlSnapshot fields
    sampled off the router's injected clock — a wall-clock read in the
    cooldown check or a sleep in an actuator would turn the controller
    selftest into a wall-time test. The whole package has an explicit
    GL007 scope entry (Config.clock_paths) and must be clock-clean
    outright — no suppressions, no baseline entries. Its
    ``mingpt_control_*`` metric families must also pass the GL008/GL009
    naming rules unsuppressed. The wall-clock shapes that would break
    replay are pinned by the gl007_control.py fixture."""
    pkg = os.path.join(REPO, "mingpt_distributed_tpu", "control")
    paths = sorted(
        os.path.join(pkg, f) for f in os.listdir(pkg) if f.endswith(".py"))
    assert len(paths) >= 5  # __init__, signals, cost, controller, importer
    cfg = Engine(select=["GL007"], root=REPO).config
    # pinned explicitly: narrowing clock_paths later must not silently
    # drop the control plane from scope
    assert "control/" in cfg.clock_paths
    for p in paths:
        rel = os.path.relpath(p, REPO)
        assert cfg.clock_in_scope(rel), f"{rel} fell out of GL007 scope"
    res = Engine(select=["GL007"], root=REPO).run(paths)
    assert not res.parse_errors
    assert res.findings == []  # not even suppressed or baselined ones

    res = Engine(select=["GL008", "GL009"], root=REPO).run(paths)
    assert not res.parse_errors
    assert res.findings == []


def test_list_rules(capsys):
    assert lint_main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for cls in all_rules():
        assert cls.id in out


def test_json_envelope(tmp_path, capsys):
    dirty = str(tmp_path / "dirty.py")
    with open(dirty, "w", encoding="utf-8") as fh:
        fh.write("import jax\n\n@jax.jit\ndef f(x):\n    return str(x)\n")
    code = lint_main(["--json", "--no-baseline", dirty])
    doc = json.loads(capsys.readouterr().out)
    assert code == 1
    assert doc["schema"] == "graftlint/1"
    assert doc["summary"]["findings"] == 1
    assert doc["summary"]["per_rule"] == {"GL002": 1}
    f = doc["findings"][0]
    assert f["rule"] == "GL002" and f["line"] == 5
    assert not f["suppressed"] and not f["baselined"]


def test_sweep_skips_fixture_corpus_but_lints_explicit_files():
    """Directory sweeps must not trip over the deliberately-violating
    corpus; naming a corpus file explicitly must still lint it."""
    sweep = run_lint([os.path.join(REPO, "tests")])
    assert not any("lint_fixtures" in f.path for f in sweep.findings)
    direct = run_lint([os.path.join(FIXTURES, "gl010_print.py")])
    assert any(f.rule_id == "GL010" for f in direct.active)


# ---------------------------------------------------------------------
# the repo-wide gate
# ---------------------------------------------------------------------


def test_lint_clean():
    """The acceptance bar: the shipped sweep over the package, tools/,
    and the top-level scripts reports zero unsuppressed findings (the
    checked-in baseline covers the grandfathered ones)."""
    proc = subprocess.run(
        [sys.executable, "-m", "mingpt_distributed_tpu.analysis"],
        cwd=REPO, capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "0 findings" in proc.stdout
