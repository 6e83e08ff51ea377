"""`BENCHMARK.json` against the contract's limits, and the harness's
refusals."""

import json
import os
import re
import subprocess
import sys

import pytest

from bh_fixtures import REPO

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        return json.load(f)


def _line_ok(s):
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_top_level_keys_and_sizes(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= len(bench["paths"]) <= 16
    assert all(PATH.match(p) and not p.startswith("/") and ".." not in p
               for p in bench["paths"])
    assert len(bench["command"]) <= 32 and all(map(_line_ok, bench["command"]))
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51
    assert 1 <= len(bench["configs"]) <= 24
    assert 1 <= len(bench["workloads"]) <= 24
    assert 1 <= len(bench["end_to_end"]) <= 16
    assert 1 <= len(bench["per_layer"]) <= 128


def test_configs(bench):
    files = set()
    used = {w["config"] for w in bench["workloads"]}
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert _line_ok(c["source"]) and _line_ok(c["why"])
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
        assert c["file"] not in files
        files.add(c["file"])
        assert len(c["reduced"]) <= 16 and all(map(NAME.match, c["reduced"]))
        with open(os.path.join(REPO, c["file"]), encoding="utf-8") as f:
            cfg = json.load(f)
        assert cfg["source"] == c["source"]
        assert set(c["reduced"]) == set(cfg["reduced"])
        assert not any(k.endswith(("_dim", "_rank")) for k in c["reduced"])
    assert len({c["source"] for c in bench["configs"]}) == len(bench["configs"])


def test_cells(bench):
    names = {c["name"] for c in bench["configs"]}
    pairs = set()
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in names and w["chips"] in (1, 4)
        assert _line_ok(w["why"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        for kind in ("workloads", "traffic"):
            name = w["name"] if kind == "workloads" else w["traffic"]
            assert os.path.isfile(os.path.join(
                REPO, "benchmark", kind, name + ".json"))
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(len(bench["workloads"]) // 2, 1)


def test_metrics(bench):
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    seen = set()
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert _line_ok(m["layer"]) and m["moves"] in e2e
        moved = e2e[m["moves"]].get("workloads", cells)
        assert set(m.get("workloads", cells)) <= set(moved), m["name"]
        desc = os.path.join(REPO, "benchmark", "layer_metrics",
                            m["name"] + ".json")
        with open(desc, encoding="utf-8") as f:
            reader = json.load(f)["reader"]
        assert os.path.isfile(os.path.join(
            REPO, "benchmark", "readers", reader + ".py"))
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["name"] not in seen
        seen.add(m["name"])
        assert set(m.get("workloads", [])) <= cells
    for w in cells:
        mine = [m for m in bench["end_to_end"]
                if w in m.get("workloads", cells)]
        assert len(mine) >= 2, f"{w} reports only setup_s"
        assert any(w in m.get("workloads", cells) for m in bench["per_layer"])


def test_run_seconds_fits_the_full_check(bench):
    rs = bench["run_seconds"]
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def _run(args, env_extra, cwd=REPO):
    env = dict(os.environ, **env_extra)
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "benchmark", "run.py")] + args,
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def test_cpu_without_a_rehearsal_fixture_exits_nonzero():
    """`JAX_PLATFORMS=cpu` and a real cell: no result line, exit != 0 —
    with and without ``--rehearsal`` (a real cell is no fixture)."""
    args = ["--workload", "taxi.stream", "--seed", "1", "--seconds", "1",
            "--trace", "0"]
    for extra in ([], ["--rehearsal"]):
        r = _run(args + extra, {"JAX_PLATFORMS": "cpu"})
        assert r.returncode != 0
        last = r.stdout.strip().splitlines()[-1]
        assert last.startswith("FAIL:") and not last.startswith("{")


def test_alone_in_a_directory_it_exits_nonzero(tmp_path):
    """Only BENCHMARK.json and the files under ``paths``: the program is
    not there, so there is nothing to measure."""
    import shutil

    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", ".traces"))
    env = {"JAX_PLATFORMS": "cpu", "PYTHONPATH": ""}
    r = _run(["--workload", "taxi.stream", "--seed", "1", "--seconds", "1",
              "--trace", "0"], env, cwd=str(tmp_path))
    assert r.returncode != 0
    assert not r.stdout.strip().splitlines()[-1].startswith("{")
