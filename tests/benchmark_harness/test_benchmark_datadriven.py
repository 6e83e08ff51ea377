"""Data-drivenness as an acceptance criterion: a new configuration, two
traffic mixes, two cells and a per-layer metric with a reader of its own
are ADDED as files to a temporary copy of the benchmark (no file that is
there is edited — `bh_fixtures.make_copy` asserts it) and run through the
unchanged harness, from that copy's own ``benchmark/run.py``.

These runs are also the CPU rehearsal of every cell's control flow: the
stream on a four-device mesh with the profiler on, the open-loop serve
with it off. A CPU run asserts answers, counts and the result line's
shape; it never states a device number."""

import json
import os
import subprocess
import sys


from bh_fixtures import REPO, make_copy

#: ``checks`` comes last: each number compared beside its limit (PR 39)
CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device",
                 "checks"}


def _run(root, args, devices):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    r = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py")] + args
        + ["--rehearsal"],
        cwd=root, env=env, capture_output=True, text=True, timeout=600,
    )
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    _numbers_compared_come_last(line, r.stderr)
    return line, r.stdout


def _numbers_compared_come_last(line, err):
    """The result line's last key and the last lines of standard error
    are the numbers compared, each beside its limit, name for name."""
    assert list(line)[-1] == "checks" and "forbidden_events" in line["checks"]
    last = err.strip().splitlines()[-len(line["checks"]):]
    for said, (name, c) in zip(last, line["checks"].items()):
        assert set(c) == {"value", "limit"}
        assert said.startswith(
            f"[check] {name}: value={c['value']!r} limit={c['limit']!r} ")
        assert said.split(" (")[0].endswith(
            "ok" if c["value"] <= c["limit"] else "FAILED")
    assert line["correct"] == all(
        c["value"] <= c["limit"] for c in line["checks"].values())


def test_new_stream_cell_on_a_mesh_traced(tmp_path):
    root = make_copy(tmp_path, mesh=4)
    line, out = _run(root, ["--workload", "tiny.stream", "--seed",
                            "4000000123", "--seconds", "1", "--trace", "1"], 4)
    assert set(line) == CONTRACT_KEYS | {"breakdown"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["attempted"] % (2 * 4 * 2048) == 0
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes", "busy_s", "window_s"}
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 4
    assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    m = line["metrics"]
    # per-layer metrics only, each {"value", "unit"}; the device-trace ones
    # find nothing to read on the CPU and are left out
    assert "rows_per_s" not in m and "setup_s" not in m
    assert m["compiles_in_window.stream"] == {"value": 0.0, "unit": "count"}
    assert m["index_build_s"]["value"] > 0 and m["warmup_s"]["value"] > 0
    assert m["launch_ms_per_dispatch.stream"]["value"] > 0
    assert "device_idle.stream" not in m and "collective_share.x4" not in m
    # the metric added as files, read by the reader added as a file
    assert m["tiny_dispatches"]["value"] == 2 * (line["attempted"] // (2 * 4 * 2048))
    assert "[check] stream_disagreement_share: value=" in out
    assert "limit=0.001" in out and "mesh={'dp': 4}" in out


def test_new_serve_cell_untraced(tmp_path):
    root = make_copy(tmp_path)
    line, out = _run(root, ["--workload", "tiny.serve", "--seed", "7",
                            "--seconds", "2", "--trace", "0"], 1)
    assert set(line) == CONTRACT_KEYS          # exactly the contract's keys
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == 80             # 40/s for 2 s, all due
    assert set(line["metrics"]) == {"latency_p50_ms", "latency_p95_ms",
                                    "setup_s"}
    for v in line["metrics"].values():
        assert set(v) == {"value", "unit"} and v["value"] > 0
    assert line["metrics"]["latency_p50_ms"]["value"] <= \
        line["metrics"]["latency_p95_ms"]["value"]
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert "compiles_in_window=0" in out
    assert "[check] serve_rows_unlike_batch_join: value=0.0 limit=0.0 ok" in out


def test_a_cell_without_its_files_is_refused(tmp_path):
    root = make_copy(tmp_path)
    os.remove(os.path.join(root, "benchmark", "traffic", "tiny-open.json"))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    r = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"),
         "--workload", "tiny.serve", "--seed", "1", "--seconds", "1",
         "--trace", "0", "--rehearsal"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert r.stdout.strip().splitlines()[-1].startswith("FAIL:")
