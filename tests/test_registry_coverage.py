"""Registry coverage: the committed registry golden and the docs agree
with what the code actually emits — the
invariant the `registry-drift` rule enforces at lint time, pinned here
in the suite with explicit known names so a silent scanner regression
(e.g. the AST scan finding nothing) cannot pass as "no drift"."""

import json
import os

from mosaic_tpu.analysis import analyze, build_registry
from mosaic_tpu.analysis.project_registry import name_matches
from mosaic_tpu.analysis.rules.drift import span_table_names

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REGISTRY = os.path.join(ROOT, "tests", "goldens", "registry.json")


def load(path):
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


def docs_text():
    chunks = [open(os.path.join(ROOT, "README.md"), encoding="utf-8").read()]
    docs = os.path.join(ROOT, "docs")
    for name in sorted(os.listdir(docs)):
        if name.endswith(".md"):
            chunks.append(
                open(os.path.join(docs, name), encoding="utf-8").read()
            )
    return "\n".join(chunks)


def test_committed_registry_matches_fresh_scan():
    fresh = build_registry(ROOT)
    committed = load(REGISTRY)
    for cat in (
        "fault_sites", "spans", "spans_tools", "events", "stages",
        "env_knobs",
    ):
        assert committed[cat] == fresh[cat], f"stale category {cat!r}"


def test_known_fault_sites_are_registered_and_documented():
    reg = load(REGISTRY)
    docs = docs_text()
    for site in (
        "pip_join.device", "stream.scan_step", "stream.snapshot",
        "stream.prefetch", "stream.admit", "serve.admit", "serve.batch",
        "serve.dispatch", "overlay.predicate", "dist_join.step",
        "knn.pair_distances",
    ):
        assert site in reg["fault_sites"], site
        assert site in docs, f"fault site {site!r} undocumented"


def test_known_dynamic_families_registered_as_wildcards():
    reg = load(REGISTRY)
    assert "join.probe.*" in reg["spans"]           # f-string span
    assert "MOSAIC_WATCHDOG_*" in reg["env_knobs"]  # per-site deadline
    assert "probe_stage.*" in reg["stages"]         # per-lane stage kwarg


def test_span_taxonomy_table_matches_code_both_ways():
    reg = load(REGISTRY)
    arch = open(
        os.path.join(ROOT, "docs", "ARCHITECTURE.md"), encoding="utf-8"
    ).read()
    table = span_table_names(arch)
    assert len(table) >= 10, "span table parse came back near-empty"
    for row in table:
        assert name_matches(row, reg["spans"]), f"stale table row {row!r}"
    for span in reg["spans"]:
        if span.endswith("*"):
            assert any(
                name_matches(row, [span]) for row in table
            ), f"span family {span!r} has no documented member"
        else:
            assert span in table, f"span {span!r} missing from the table"


def test_env_knobs_are_documented():
    reg = load(REGISTRY)
    docs = docs_text()
    assert reg["env_knobs"], "scan found no env knobs"
    for knob in reg["env_knobs"]:
        probe = knob[:-1] if knob.endswith("*") else knob
        assert probe in docs, f"env knob {knob!r} undocumented"


def test_registry_drift_rule_is_green_on_the_repo():
    res = analyze(ROOT, rule_names=["registry-drift"])
    assert res.findings == [], [f.render() for f in res.findings]
