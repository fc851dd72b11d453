"""Program-contract checker (``poisson_tpu.contracts``).

The contract under test, layer by layer:

- **every lint rule fires and suppresses** — one positive fixture and
  one suppressed-negative fixture per rule, through the
  ``lint_source`` seam (synthetic sources, no tree dependency);
- **the tree is clean** — ``run_lint`` + ``run_drift`` on this
  checkout report zero unsuppressed findings (the PR's own acceptance
  criterion: the lint lands with zero unexplained suppressions);
- **the ledger holds and bites** — the committed ``ledger.json``
  matches the current lowerings (round trip), and a deliberately
  mutated flag-off program (a stream callback forced in) is caught
  both structurally (forbidden ``custom_call``) and by fingerprint;
- **drift detection bites** — an injected policy field produces a
  finding, and the exemption allowlist silences it with a reason;
- **the gate is the gate** — ``python -m poisson_tpu.contracts
  --json`` exits 0 on this tree (the tier-1 hook: a contract break
  fails the suite, not just a human review).
"""

import json
import os
import subprocess
import sys

import pytest

from poisson_tpu.contracts.hlo import (
    CALLBACK_MARKERS,
    assert_no_forbidden,
    find_forbidden,
    hlo_fingerprint,
    strip_hlo_metadata,
)
from poisson_tpu.contracts.lint import (
    RULES,
    documented_metric_names,
    lint_source,
    repo_root,
    run_lint,
)

pytestmark = pytest.mark.contracts

ROOT = repo_root()


def _rules(findings, suppressed=None):
    return sorted({f.rule for f in findings
                   if suppressed is None or f.suppressed == suppressed})


# -- lint rules: positive + suppressed-negative fixtures ----------------


def test_callback_gate_fires_and_suppresses():
    bad = (
        "import jax\n"
        "def body(s):\n"
        "    jax.debug.print('k={}', s.k)\n"
        "    return s\n"
    )
    found = lint_source("poisson_tpu/solvers/pcg.py", bad)
    assert "callback-gate" in _rules(found, suppressed=False)

    gated = (
        "import jax\n"
        "def factory(stream_every):\n"
        "    def body(s):\n"
        "        if stream_every > 0:\n"
        "            jax.debug.print('k={}', s.k)\n"
        "        return s\n"
        "    return body\n"
    )
    assert not lint_source("poisson_tpu/solvers/pcg.py", gated)

    cond_gated = (
        "import jax\n"
        "from jax import lax\n"
        "def emit(due, k):\n"
        "    lax.cond(due, lambda: jax.debug.callback(print, k),\n"
        "             lambda: None)\n"
    )
    assert not lint_source("poisson_tpu/obs/stream.py", cond_gated)

    suppressed = (
        "import jax\n"
        "def body(s):\n"
        "    # contracts: allow=callback-gate -- diagnostic build only\n"
        "    jax.debug.print('k={}', s.k)\n"
        "    return s\n"
    )
    found = lint_source("poisson_tpu/solvers/pcg.py", suppressed)
    assert _rules(found, suppressed=True) == ["callback-gate"]
    assert found[0].reason == "diagnostic build only"


def test_traced_branch_fires_and_suppresses():
    bad = (
        "from jax import lax\n"
        "def loop(init, cap):\n"
        "    def body(s):\n"
        "        if s.done:\n"
        "            return s\n"
        "        return step(s)\n"
        "    def cond(s):\n"
        "        return s.k < cap\n"
        "    return lax.while_loop(cond, body, init)\n"
    )
    found = lint_source("poisson_tpu/solvers/pcg.py", bad)
    assert "traced-branch" in _rules(found, suppressed=False)

    ok = bad.replace("if s.done:", "if cap > 0:").replace(
        "            return s\n        return step(s)\n",
        "            return step(s)\n        return s\n")
    assert not lint_source("poisson_tpu/solvers/pcg.py", ok)

    sup = bad.replace(
        "        if s.done:",
        "        # contracts: allow=traced-branch -- concrete-only helper\n"
        "        if s.done:")
    found = lint_source("poisson_tpu/solvers/pcg.py", sup)
    assert _rules(found, suppressed=False) == []


def test_traced_while_fires():
    bad = (
        "from jax import lax\n"
        "def loop(init):\n"
        "    def body(s):\n"
        "        while s.k < 3:\n"
        "            s = step(s)\n"
        "        return s\n"
        "    return lax.while_loop(lambda s: s.k < 9, body, init)\n"
    )
    found = lint_source("poisson_tpu/solvers/pcg.py", bad)
    assert "traced-branch" in _rules(found, suppressed=False)


def test_static_default_fires_and_suppresses():
    bad = (
        "import functools, jax\n"
        "@functools.partial(jax.jit, static_argnums=(0,))\n"
        "def f(cfg=[], x=None):\n"
        "    return x\n"
    )
    found = lint_source("poisson_tpu/solvers/pcg.py", bad)
    assert "static-default" in _rules(found, suppressed=False)

    ok = bad.replace("cfg=[]", "cfg=()")
    assert not lint_source("poisson_tpu/solvers/pcg.py", ok)

    plain_mutable = (
        "def g(acc={}):\n"
        "    return acc\n"
    )
    found = lint_source("poisson_tpu/solvers/pcg.py", plain_mutable)
    assert "static-default" in _rules(found, suppressed=False)

    sup = bad.replace(
        "def f(cfg=[], x=None):",
        "def f(cfg=[], x=None):  "
        "# contracts: allow=static-default -- test fixture")
    assert not _rules(lint_source("poisson_tpu/solvers/pcg.py", sup),
                      suppressed=False)


def test_static_default_positional_only_and_kwonly():
    """args.defaults spans posonly+args and kw-only params carry their
    own defaults — neither placement hides a mutable default, and the
    posonly layout must not misattribute the finding."""
    posonly = (
        "def f(cfg=[], /, x=()):\n"
        "    return x\n"
    )
    found = lint_source("poisson_tpu/solvers/pcg.py", posonly)
    assert len(found) == 1 and "cfg" in found[0].message

    kwonly = (
        "def g(*, acc=[]):\n"
        "    return acc\n"
    )
    found = lint_source("poisson_tpu/solvers/pcg.py", kwonly)
    assert [f.rule for f in found] == ["static-default"]
    assert "acc" in found[0].message


def test_suppression_pattern_in_strings_is_inert():
    """The suppression syntax inside a docstring or string literal is
    documentation, not a live suppression — it must neither suppress a
    real finding nor fire suppression-reason."""
    doc_example = (
        '"""Docs.\n'
        "\n"
        "Example: # contracts: allow=wallclock\n"
        '"""\n'
        "def f():\n"
        "    return 1\n"
    )
    assert not lint_source("poisson_tpu/solvers/pcg.py", doc_example)

    fake_shield = (
        "import time\n"
        "def setup():\n"
        "    msg = '# contracts: allow=all -- x'\n"
        "    return time.time(), msg\n"
    )
    found = lint_source("poisson_tpu/solvers/pcg.py", fake_shield)
    assert _rules(found, suppressed=False) == ["wallclock"]


def test_wallclock_and_rng_fire_and_scope():
    bad = (
        "import time, random\n"
        "import numpy as np\n"
        "def setup():\n"
        "    t0 = time.time()\n"
        "    jitter = random.random()\n"
        "    noise = np.random.normal()\n"
        "    return t0 + jitter + noise\n"
    )
    found = lint_source("poisson_tpu/solvers/pcg.py", bad)
    assert _rules(found, suppressed=False) == ["rng", "wallclock"]
    # out of solver scope: the same source is fine in serve/
    assert not lint_source("poisson_tpu/serve/service.py", bad)
    # seeded generators pass
    seeded = (
        "import numpy as np\n"
        "def setup(seed):\n"
        "    return np.random.default_rng(seed).normal()\n"
    )
    assert not lint_source("poisson_tpu/solvers/pcg.py", seeded)
    # the watchdog is exempt: wall-clock supervision is its job
    assert not lint_source("poisson_tpu/parallel/watchdog.py", bad)


def test_counter_doc_fires_against_catalogue():
    ctx = {
        "metric_names": documented_metric_names(
            '"""Counters:\n'
            "- ``pcg.solves.<verdict>`` and ``serve.shed.{a,b}`` and\n"
            "  ``plain.counter``.\n"
            '"""\n'),
        "flight_kinds": set(),
    }
    src = (
        "from poisson_tpu import obs\n"
        "def f(tag):\n"
        "    obs.inc('plain.counter')\n"       # documented
        "    obs.inc('serve.shed.a')\n"        # brace-expanded
        "    obs.inc(f'pcg.solves.{tag}')\n"   # wildcard family
        "    obs.inc('rogue.counter')\n"       # undocumented
    )
    found = lint_source("poisson_tpu/serve/service.py", src, ctx)
    assert [f.rule for f in found] == ["counter-doc"]
    assert "rogue.counter" in found[0].message

    sup = src.replace(
        "    obs.inc('rogue.counter')\n",
        "    # contracts: allow=counter-doc -- migration shim\n"
        "    obs.inc('rogue.counter')\n")
    assert not _rules(lint_source("poisson_tpu/serve/service.py", sup,
                                  ctx), suppressed=False)


def test_flight_kind_fires_against_declared_kinds():
    ctx = {"metric_names": (set(), set()),
           "flight_kinds": {"queue_wait", "retry"}}
    src = (
        "def f(self, rid):\n"
        "    self._flight.begin(rid, 'queue_wait')\n"
        "    self._flight.point(rid, 'undeclared_kind')\n"
    )
    found = lint_source("poisson_tpu/serve/service.py", src, ctx)
    assert [f.rule for f in found] == ["flight-kind"]
    assert "undeclared_kind" in found[0].message
    # constants (Name refs) are fine — only rogue literals fire
    const = "def f(self, rid):\n    self._flight.point(rid, POINT_X)\n"
    assert not lint_source("poisson_tpu/serve/service.py", const, ctx)


def test_chaos_registry_fires_for_unregistered_scenario():
    src = (
        "def _registered(seed):\n"
        "    return {}\n"
        "def _forgotten(seed):\n"
        "    return {}\n"
    )
    src = ("@scenario('reg')\n" + src.split("def _forgotten")[0]
           + "def _forgotten" + src.split("def _forgotten")[1])
    found = lint_source("poisson_tpu/testing/chaos.py", src)
    assert [f.rule for f in found] == ["chaos-registry"]
    assert "_forgotten" in found[0].message
    # other files: the rule never looks
    assert not lint_source("poisson_tpu/serve/service.py", src)


def test_fingerprint_key_fires_in_key_builders():
    src = (
        "def dispatch(problem, spec, size, dtype_name):\n"
        "    key = (size, problem, dtype_name, spec.fingerprint)\n"
        "    return key\n"
    )
    found = lint_source("poisson_tpu/solvers/batched.py", src)
    assert [f.rule for f in found] == ["fingerprint-key"]

    clean = src.replace(", spec.fingerprint", ", 'geo'")
    assert not lint_source("poisson_tpu/solvers/batched.py", clean)

    cohort = (
        "def _cohort(self, request):\n"
        "    return request.geometry.fingerprint\n"
    )
    found = lint_source("poisson_tpu/serve/service.py", cohort)
    assert [f.rule for f in found] == ["fingerprint-key"]


def test_suppression_without_reason_is_a_finding():
    src = (
        "import time\n"
        "def setup():\n"
        "    # contracts: allow=wallclock\n"
        "    return time.time()\n"
    )
    found = lint_source("poisson_tpu/solvers/pcg.py", src)
    assert "suppression-reason" in _rules(found)
    # the reasonless allow still suppresses the underlying finding —
    # but leaves the louder meta-finding, so the gate stays red
    assert _rules(found, suppressed=False) == ["suppression-reason"]


# -- the tree itself is clean ------------------------------------------


def test_tree_lint_is_clean():
    rep = run_lint(ROOT)
    active = [f for f in rep["findings"] if not f["suppressed"]]
    assert active == [], "\n".join(
        f"{f['file']}:{f['line']}: [{f['rule']}] {f['message']}"
        for f in active)
    assert rep["counts"]["rules"] >= 8


def test_tree_drift_is_clean():
    from poisson_tpu.contracts.drift import run_drift

    rep = run_drift(ROOT)
    assert rep["findings"] == [], "\n".join(
        f"{f['file']}:{f['line']}: {f['message']}"
        for f in rep["findings"])


def test_every_rule_has_a_fixture_here():
    """The rule list and this test file move together."""
    src = open(__file__).read()
    for rule in RULES:
        assert rule in src, f"rule {rule} has no fixture in this file"


# -- canonicalization / structural helpers ------------------------------


def test_strip_hlo_metadata_both_dialects():
    compiled = 'add = f64[] add(a, b), metadata={op_name="jit(f)/add"}'
    assert strip_hlo_metadata(compiled) == "add = f64[] add(a, b)"
    stable = ('%0 = stablehlo.add %a, %b : tensor<f64> '
              'loc("jit(f)"("x.py":1:0))\n#loc1 = loc("x.py":2:0)\n')
    out = strip_hlo_metadata(stable)
    assert "loc(" not in out and "#loc" not in out
    assert "stablehlo.add" in out


def test_find_forbidden_and_assert():
    txt = "stablehlo.custom_call @xla_ffi_python_cpu_callback(...)"
    assert find_forbidden(txt, CALLBACK_MARKERS) \
        == ["custom_call", "callback"]
    with pytest.raises(AssertionError, match="custom_call"):
        assert_no_forbidden(txt, CALLBACK_MARKERS, context="fixture")
    assert_no_forbidden("stablehlo.add", CALLBACK_MARKERS)


def test_fingerprint_ignores_metadata_only_differences():
    a = 'op = f64[] add(a, b), metadata={op_name="x"}'
    b = 'op = f64[] add(a, b), metadata={op_name="y"}'
    assert hlo_fingerprint(a) == hlo_fingerprint(b)
    assert hlo_fingerprint(a) != hlo_fingerprint("op = f64[] add(a, c)")


# -- the HLO identity ledger -------------------------------------------


def test_ledger_round_trip_matches_committed():
    """Every registered program lowers to exactly the committed
    fingerprint — the 11-test-files' byte-pins, now one harness."""
    from poisson_tpu.contracts.manifest import run_ledger_check

    report = run_ledger_check()
    assert report["programs"] >= 6
    assert report["problems"] == [], report["problems"]


def test_ledger_detects_a_mutated_flag_off_program():
    """Force a callback into the flagship flag-off program (lower the
    jitted ``_solve`` with ``stream_every=5``): the ledger harness must
    catch it BOTH ways — structurally (forbidden custom_call/callback)
    and by fingerprint drift against the committed entry."""
    from poisson_tpu.contracts.manifest import load_ledger, markers_for
    from poisson_tpu.solvers.pcg import _solve, host_setup
    from poisson_tpu.config import Problem

    p = Problem(M=20, N=24)
    a, b, rhs, aux = host_setup(p, "float64", False)
    mutated = _solve.lower(p, False, 5, 0, 0.0, False, 0,
                           a, b, rhs, aux).as_text()
    assert find_forbidden(mutated, markers_for(("callbacks",)))
    committed = load_ledger()["entries"]["solve.jacobi_f64"]
    assert hlo_fingerprint(mutated) != committed["fingerprint"]


def test_ledger_update_writes_and_recheck_is_stable(tmp_path):
    from poisson_tpu.contracts.manifest import run_ledger_check

    path = str(tmp_path / "ledger.json")
    first = run_ledger_check(update=True, path=path)
    assert first["updated"] and os.path.exists(path)
    second = run_ledger_check(path=path)
    assert second["problems"] == []
    data = json.load(open(path))
    assert set(data["entries"]) == set(first["entries"])
    # determinism: the fingerprints reproduce within a process
    assert {k: v["fingerprint"] for k, v in data["entries"].items()} \
        == {k: v["fingerprint"] for k, v in second["entries"].items()}


def test_gate_exits_one_when_a_covered_program_drifts(tmp_path,
                                                      monkeypatch,
                                                      capsys):
    """The acceptance criterion end to end: tamper with a covered
    program's committed fingerprint (equivalent to its lowering having
    changed under the gate) and the `python -m poisson_tpu.contracts`
    entry point flips to exit 1 with a ledger-drift problem naming the
    program."""
    from poisson_tpu.contracts import manifest
    from poisson_tpu.contracts.__main__ import main

    data = dict(manifest.load_ledger())
    data["entries"] = dict(data["entries"])
    data["entries"]["solve.jacobi_f64"] = {
        **data["entries"]["solve.jacobi_f64"],
        "fingerprint": "f" * 64,
    }
    path = str(tmp_path / "tampered.json")
    json.dump(data, open(path, "w"))
    monkeypatch.setattr(manifest, "LEDGER_PATH", path)
    rc = main(["--json"])
    report = json.loads(capsys.readouterr().out)
    assert rc == 1 and report["ok"] is False
    drifted = [p for p in report["ledger"]["problems"]
               if p["kind"] == "ledger-drift"]
    assert [p["program"] for p in drifted] == ["solve.jacobi_f64"]


def test_absent_or_corrupt_ledger_fails_the_gate(tmp_path):
    """A gate that silently stopped producing evidence is not a
    passing gate: no committed ledger (or an unreadable one) is a
    ledger-absent problem, never a green check."""
    from poisson_tpu.contracts.manifest import run_ledger_check

    missing = run_ledger_check(path=str(tmp_path / "nope.json"))
    assert [p["kind"] for p in missing["problems"]] == ["ledger-absent"]
    corrupt = tmp_path / "corrupt.json"
    corrupt.write_text("{not json")
    report = run_ledger_check(path=str(corrupt))
    assert [p["kind"] for p in report["problems"]] == ["ledger-absent"]


def test_from_imports_cannot_evade_purity_rules():
    """`from time import perf_counter` / `from jax import debug` must
    resolve through the import bindings — the ordinary from-import
    idiom is not a lint bypass."""
    wall = (
        "from time import perf_counter\n"
        "def setup():\n"
        "    return perf_counter()\n"
    )
    found = lint_source("poisson_tpu/solvers/pcg.py", wall)
    assert _rules(found) == ["wallclock"]

    cb = (
        "from jax import debug\n"
        "def body(s):\n"
        "    debug.print('k={}', s.k)\n"
        "    return s\n"
    )
    found = lint_source("poisson_tpu/solvers/pcg.py", cb)
    assert "callback-gate" in _rules(found)

    aliased = (
        "from time import time as now\n"
        "def setup():\n"
        "    return now()\n"
    )
    found = lint_source("poisson_tpu/solvers/pcg.py", aliased)
    assert _rules(found) == ["wallclock"]


def test_drift_missing_sources_fail_loudly(tmp_path):
    """run_drift on a root without the checked files reports findings
    (drift-source-missing), never a crash and never a silent pass."""
    from poisson_tpu.contracts.drift import run_drift

    rep = run_drift(str(tmp_path))
    rules = {f["rule"] for f in rep["findings"]}
    assert rules == {"drift-source-missing"}
    assert len(rep["findings"]) == 2


def test_ledger_flags_missing_and_stale_entries(tmp_path):
    from poisson_tpu.contracts.manifest import (
        LEDGER_SCHEMA,
        load_ledger,
        run_ledger_check,
    )

    data = dict(load_ledger())
    entries = dict(data["entries"])
    victim = sorted(entries)[0]
    entries.pop(victim)
    entries["ghost.program"] = {"fingerprint": "0" * 64}
    path = str(tmp_path / "ledger.json")
    json.dump({**data, "schema": LEDGER_SCHEMA, "entries": entries},
              open(path, "w"))
    report = run_ledger_check(path=path)
    kinds = {p["kind"]: p["program"] for p in report["problems"]}
    assert kinds.get("ledger-missing") == victim
    assert kinds.get("ledger-stale") == "ghost.program"


# -- registry drift detection ------------------------------------------


def test_policy_coverage_drift_fires_and_exempts():
    from poisson_tpu.contracts.drift import check_policy_coverage

    types_src = (
        "import dataclasses\n"
        "@dataclasses.dataclass(frozen=True)\n"
        "class ServicePolicy:\n"
        "    capacity: int = 64\n"
        "    novel_knob: int = 0\n"
    )
    chaos_src = "svc = SolveService(ServicePolicy(capacity=16))\n"
    found = check_policy_coverage(types_src, chaos_src, exempt={})
    assert [f.rule for f in found] == ["policy-chaos-coverage"]
    assert "novel_knob" in found[0].message
    assert not check_policy_coverage(
        types_src, chaos_src,
        exempt={"ServicePolicy.novel_knob": "covered elsewhere"})
    exercised = chaos_src.replace("capacity=16",
                                  "capacity=16, novel_knob=1")
    assert not check_policy_coverage(types_src, exercised, exempt={})
    # an exemption for a field that no longer exists is rot
    found = check_policy_coverage(
        types_src, exercised,
        exempt={"ServicePolicy.removed_knob": "was covered elsewhere"})
    assert [f.rule for f in found] == ["exemption-stale"]


# -- the gate -----------------------------------------------------------


def test_contracts_gate_exits_zero_on_this_tree():
    """The tier-1 hook: a contract break anywhere fails this test, not
    just a human review."""
    proc = subprocess.run(
        [sys.executable, "-m", "poisson_tpu.contracts", "--json"],
        capture_output=True, text=True, cwd=ROOT, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    report = json.loads(proc.stdout)
    assert report["ok"] is True
    assert report["counts"]["rules"] >= 8
    assert report["counts"]["findings"] == 0
    assert report["counts"]["ledger_problems"] == 0
    assert report["counts"]["ledger_programs"] >= 6


def test_contracts_lint_only_gate():
    proc = subprocess.run(
        [sys.executable, "-m", "poisson_tpu.contracts", "--lint-only",
         "--json"],
        capture_output=True, text=True, cwd=ROOT, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    report = json.loads(proc.stdout)
    assert report["ledger"] is None and report["ok"] is True


def test_contracts_gauges_stamped():
    from poisson_tpu.contracts.__main__ import run_contracts
    from poisson_tpu.obs import metrics

    report = run_contracts(ROOT, ledger=False)
    assert report["ok"]
    snap = metrics.snapshot()["gauges"]
    assert snap["contracts.findings"] == 0
    assert snap["contracts.rules"] >= 8


def test_contracts_gauges_survive_exposition():
    from poisson_tpu.contracts.__main__ import run_contracts
    from poisson_tpu.obs import export

    report = run_contracts(ROOT, ledger=False)
    parsed = export.parse_text(export.render())
    assert parsed["poisson_tpu_contracts_findings"] == {"type": "gauge",
                                                        "value": 0.0}
    assert parsed["poisson_tpu_contracts_rules"]["value"] \
        == report["counts"]["rules"]


def test_gate_reads_nothing_outside_the_package(tmp_path):
    """The gate's inputs are the package alone: on a root that holds
    only a copy of ``poisson_tpu/`` it passes over the same files."""
    import shutil

    from poisson_tpu.contracts.__main__ import run_contracts

    shutil.copytree(os.path.join(ROOT, "poisson_tpu"),
                    tmp_path / "poisson_tpu",
                    ignore=shutil.ignore_patterns("__pycache__"))
    alone = run_contracts(str(tmp_path), ledger=False)
    assert alone["ok"], alone["findings"]
    assert alone["files"] == run_contracts(ROOT, ledger=False)["files"]
