"""`chip_smoke.py` as a process: its exit codes are its contract.

Named to sort last: tier-1 runs the files in name order under a hard time
limit, and this one spends ~25 s compiling every phase in a child process —
if the limit ever bites, it costs this file and nothing that was there before.
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _smoke(*args, env_update=None, env_drop=()):
    env = dict(os.environ)
    for k in env_drop:
        env.pop(k, None)
    env.update(env_update or {})
    r = subprocess.run(
        [sys.executable, SMOKE, *args], env=env, cwd=REPO,
        capture_output=True, text=True, timeout=600,
    )
    lines = r.stdout.strip().splitlines()
    return r.returncode, (lines[-1] if lines else ""), r.stdout


def _report(out):
    """The per-phase report: the line before the result line."""
    line = out.strip().splitlines()[-2]
    assert line.startswith("report: "), line
    return json.loads(line[len("report: "):])


def test_chip_smoke_tiny_passes_on_an_explicit_cpu():
    """Every phase at the tiny size, the mesh phase on the suite's eight
    virtual devices (XLA_FLAGS is inherited from conftest)."""
    rc, last, out = _smoke("--tiny", env_update={"JAX_PLATFORMS": "cpu"})
    assert rc == 0, out[-2000:]
    # the last line is the contract's result line: these keys, no other
    assert json.loads(last) == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 8},
    }
    doc = _report(out)
    for phase in ("batch", "stream", "serve", "kernels", "mesh"):
        assert phase in doc, phase
    assert doc["batch"]["agreement_recheck"] == 1.0
    assert doc["stream"]["overflow"] == 0
    assert doc["serve"]["store_warmed_backend_compiles"] == 0
    assert doc["mesh"]["shards_per_output"] == 8


def test_chip_smoke_never_takes_the_cpu_by_itself():
    # the plain command refuses the CPU even where the environment asks
    # for it (this sandbox's does): there is no result line, the reason
    # is the last line, and the exit code is not 0
    rc, last, out = _smoke(env_update={"JAX_PLATFORMS": "cpu"})
    assert rc != 0 and last.startswith("FAIL: PlatformError"), out[-2000:]
    assert '"ok"' not in out
    # --tiny on a CPU nobody asked for (JAX found no accelerator and fell
    # back) is refused the same way
    rc, last, out = _smoke("--tiny", env_drop=("JAX_PLATFORMS",))
    assert rc != 0 and last.startswith("FAIL: PlatformError"), out[-2000:]
    assert '"ok"' not in out
