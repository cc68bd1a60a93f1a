"""Ops-tool contracts (tools/): the source linters that tier-1 runs."""

import pytest


def test_metric_names_follow_convention():
    """mmlspark_<subsystem>_<name>_<unit> over the whole tree — drift in
    a metric name breaks dashboards/alerts silently, so it fails here."""
    from tools.lint_metric_names import MIN_EXPECTED, lint

    violations, seen = lint()
    assert not violations, violations
    assert seen >= MIN_EXPECTED, (
        f"only {seen} registrations found — the linter's scan regex no "
        "longer matches the registration idiom"
    )


def test_metric_name_linter_catches_violations(tmp_path):
    from tools.lint_metric_names import lint

    bad = tmp_path / "bad.py"
    bad.write_text(
        'c = obs.counter("mmlspark_serving_oops")\n'          # no unit
        'g = obs.gauge("mmlspark_nonexistent_thing_total")\n'  # bad subsystem
        'h = obs.histogram("mmlspark_gbdt_round_seconds")\n'   # ok
    )
    violations, seen = lint([str(bad)])
    assert seen == 3
    assert sorted(v[1] for v in violations) == [
        "mmlspark_nonexistent_thing_total", "mmlspark_serving_oops",
    ]


def test_metric_name_linter_knows_slo_subsystem(tmp_path):
    """The SLO engine's families (obs/slo.py) are a first-class
    subsystem: burn-rate gauges pass, and the subsystem list the error
    message advertises includes it."""
    from tools.lint_metric_names import SUBSYSTEMS, lint

    assert "slo" in SUBSYSTEMS
    src = tmp_path / "slo.py"
    src.write_text(
        'b = obs.gauge("mmlspark_slo_burn_rate_ratio")\n'
        'c = obs.counter("mmlspark_slo_evaluations_total")\n'
        'bad = obs.gauge("mmlspark_slo_burn_rate")\n'  # no unit suffix
    )
    violations, seen = lint([str(src)])
    assert seen == 3
    assert [v[1] for v in violations] == ["mmlspark_slo_burn_rate"]


def test_fault_points_all_exercised_by_tests():
    """Every faults.inject() point in the production tree must be named
    by at least one test — untested recovery machinery has never been
    watched recovering (tools/lint_fault_points.py)."""
    from tools.lint_fault_points import MIN_EXPECTED, lint

    violations, seen = lint()
    assert not violations, violations
    assert seen >= MIN_EXPECTED, (
        f"only {seen} injection points found — the linter's scan regex "
        "no longer matches the inject() idiom"
    )


def test_fault_point_linter_catches_unexercised_point(tmp_path):
    from tools.lint_fault_points import lint

    prod = tmp_path / "prod.py"
    prod.write_text(
        'faults.inject("elastic.detect", context={})\n'     # exercised
        'inject("zzz.never_tested")\n'                      # not
    )
    tests_file = tmp_path / "test_x.py"
    tests_file.write_text('plan.on("elastic.detect", payload=1)\n')
    violations, seen = lint([str(prod)], [str(tests_file)])
    assert seen == 2
    assert [v[0] for v in violations] == ["zzz.never_tested"]


def test_wire_rule_kinds_all_exercised_by_tests():
    """Every ChaosProxy rule kind (chaos/wire.py RULE_KINDS) must be
    named by at least one test — an untested wire fault is an adversary
    nobody has ever watched the fleet survive."""
    from tools.lint_fault_points import (
        MIN_EXPECTED_KINDS,
        lint_chaos_rules,
        wire_rule_kinds,
    )

    kinds = wire_rule_kinds()
    assert len(kinds) >= MIN_EXPECTED_KINDS, (
        f"only {len(kinds)} wire rule kinds extracted — the RULE_KINDS "
        "regex no longer matches chaos/wire.py"
    )
    assert "flip" in kinds and "blackhole" in kinds
    untested, n = lint_chaos_rules()
    assert n == len(kinds)
    assert untested == [], untested


def test_wire_rule_linter_catches_untested_kind(tmp_path):
    from tools.lint_fault_points import lint_chaos_rules

    tests_file = tmp_path / "test_x.py"
    # names every kind except truncate_rst
    tests_file.write_text(
        'WireRule("latency"); "throttle flip slowdrip blackhole"\n'
    )
    untested, n = lint_chaos_rules(test_paths=[str(tests_file)])
    assert n >= 6
    assert untested == ["truncate_rst"]


# The histogram kernels' and growers' environment knobs that had one value
# in use (PR 29): block sizes, kernel variants, pool tuning. Each is now a
# constant or derived from the call; none may come back as a read.
_REMOVED_KNOBS = (
    "HIST_DF", "HIST_NC", "HIST_SPLIT_DF", "HIST_SPLIT", "HIST_VMEM_MB",
    "GBDT_SIBLING", "GBDT_VECTOR_SPLIT", "CPU_ASYNC_DISPATCH",
    "HIST_POOL_MIN", "HIST_POOL_SPIN_S", "HIST_POOL_CTX",
)


@pytest.mark.parametrize("knob", _REMOVED_KNOBS)
def test_removed_histogram_knob_is_read_nowhere(knob):
    import pathlib
    import re

    root = pathlib.Path(__file__).resolve().parent.parent
    name = re.compile("MMLSPARK_TPU_" + knob + r"(?![A-Z0-9_])")
    files = [root / "chip_smoke.py", root / "__graft_entry__.py"]
    for sub in ("mmlspark_tpu", "tools"):
        files += sorted((root / sub).rglob("*.py"))
    assert len(files) > 100
    hits = [str(p.relative_to(root)) for p in files if name.search(p.read_text())]
    assert not hits, hits
