"""chip_smoke.py on the CPU: what it must refuse, and what it must not miss.

The script's job is to prove the device path runs on a TPU, so most of what
can be pinned here is how it fails: no accelerator, no checkout, and — the
case the script exists for — a device step that failed, was replayed on the
host by the DeviceGuard, and left rows that are nonetheless right.
"""

import os
import shutil
import subprocess
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")
sys.path.insert(0, REPO)


def _run(args, cwd, **env):
    """A fresh interpreter on the CPU; an env value of None unsets it."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **env}
    return subprocess.run(
        [sys.executable, *args], capture_output=True, text=True, timeout=280,
        cwd=cwd, env={k: v for k, v in env.items() if v is not None})


def test_no_accelerator_is_a_failure_that_names_the_platform(tmp_path):
    p = _run([SMOKE], REPO)
    assert p.returncode != 0
    assert "platform 'cpu'" in p.stderr and "stage device" in p.stderr
    assert p.stdout == ""               # no result of any kind
    # alone in a directory, past the platform gate: still a failure
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    p = _run(["chip_smoke.py", "--rehearsal"], tmp_path, PYTHONPATH="")
    assert p.returncode != 0
    assert "stage import" in p.stderr and '"ok"' not in p.stdout


def test_guard_replay_fails_the_stage_though_the_rows_are_right():
    """The hidden fallback, pinned: one device step raises, the DeviceGuard
    replays its batch on a host tier, every row still equals the
    interpreter's — and the served check fails on the guard's counters, the
    probe and the logged warning."""
    import logging

    import jax

    import chip_smoke as cs

    n = 300
    rng = np.random.default_rng(3)
    cols = {"auction": rng.integers(0, 20, n).astype(np.int32),
            "bidder": rng.integers(0, 100, n).astype(np.int32),
            "price": rng.integers(0, 401, n) / 4.0}
    ts = 1_000_000 + np.arange(n, dtype=np.int64)
    platform = jax.devices()[0].platform

    def feed(rt):
        rt.input_handler("Bids").send_columns(cols, ts)

    def sabotaged(rt):
        compiled = rt.device_bridges[0].runtime.compiled
        real, calls = compiled.step, []

        def step(state, batch):
            calls.append(1)
            if len(calls) == 1:
                raise RuntimeError("the chip's compiler refused this kernel")
            return real(state, batch)

        compiled.step = step
        feed(rt)

    def check(rt):
        return cs.served_failures(rt, n, platform)

    ann = "@device(strict='true', batch='512')"     # one batch: one replay
    ref, _ = cs.run_app(cs.S1_APP.format(device=""), "Stats", feed)
    warnings = cs._Warnings()
    logging.getLogger("siddhi_tpu").addHandler(warnings)
    try:
        rows, bad = cs.run_app(cs.S1_APP.format(device=ann), "Stats", feed,
                               check)
        assert bad == [] and warnings.drain() == []
        assert cs.rows_failures(ref, rows, ordered=True) == []

        rows, bad = cs.run_app(cs.S1_APP.format(device=ann), "Stats",
                               sabotaged, check)
        logged = warnings.drain()
    finally:
        logging.getLogger("siddhi_tpu").removeHandler(warnings)
    assert len(ref) > 200
    assert cs.rows_failures(ref, rows, ordered=True) == []     # rows right
    assert any("guard.failures == 1" in b for b in bad), bad
    assert any(f"guard.fallback_events == {n}" in b for b in bad), bad
    assert any("probe saw no device step" in b for b in bad), bad
    assert any("device step failed" in w for w in logged), logged


def test_compile_cache_is_placed_from_outside_or_in_the_checkout(tmp_path):
    show = ("from siddhi_tpu.tpu.compile_cache import enable_compile_cache; "
            "import jax; d = enable_compile_cache(); "
            "assert d == jax.config.jax_compilation_cache_dir; print(d)")
    placed = str(tmp_path / "placed")
    p = _run(["-c", show], REPO, JAX_COMPILATION_CACHE_DIR=placed)
    assert p.stdout.strip() == placed, p.stderr[-2000:]
    p = _run(["-c", show], tmp_path, JAX_COMPILATION_CACHE_DIR=None,
             PYTHONPATH=REPO)
    assert p.stdout.strip() == os.path.join(REPO, ".jax_cache"), \
        p.stderr[-2000:]


def test_rehearsal_runs_green_on_cpu(tmp_path):
    """Every stage at the reduced size (S4 on the forced-host devices
    conftest.py sets up): exit 0, every line marked as a rehearsal, no result
    line, and the cache where the environment put it."""
    cache = tmp_path / "cache"
    in_checkout = os.path.exists(os.path.join(REPO, ".jax_cache"))
    p = _run([SMOKE, "--rehearsal"], REPO,
             JAX_COMPILATION_CACHE_DIR=str(cache))
    assert p.returncode == 0, p.stdout[-4000:] + p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    assert all(ln.startswith("[REHEARSAL reduced size on cpu") for ln in lines)
    assert not any('"ok"' in ln for ln in lines)
    for stage in ("S1: ok", "S2: ok", "S3: ok", "S4: ok", "S5: ok",
                  "S6: ok"):
        assert any(stage in ln for ln in lines), (stage, p.stdout[-4000:])
    assert any('"ingress": "native"' in ln for ln in lines)
    assert os.listdir(cache)
    assert os.path.exists(os.path.join(REPO, ".jax_cache")) == in_checkout
