"""chip_smoke.py's two faces on a machine with no TPU: the labelled CPU
dry run passes end to end, and the real invocation fails on the
platform check — it never reports a CPU run as a chip result."""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(*argv, timeout):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    # conftest's 8 virtual devices are for in-process sharding tests;
    # the smoke's children see the machine as a user's shell would
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py"), *argv],
        env=env, cwd=ROOT, capture_output=True, text=True,
        timeout=timeout)


def test_dry_run_passes_on_cpu_and_says_so():
    r = _run("--allow-cpu", "--rows", "65536", timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    lines = r.stdout.strip().splitlines()
    summary = json.loads(lines[-1])
    assert summary["dry_run"] is True
    assert summary["platform"] == "cpu"
    assert summary["claim"] is None
    # the {"ok", "device"} result line belongs to a chip run alone
    assert not any("ok" in json.loads(ln) for ln in lines)
    # what a CPU run cannot show is reported skipped, never passed
    skipped = summary["checks_skipped"]
    assert "platform==tpu" in skipped
    assert "phase B: every launch is pallas_hash" in skipped
    assert any(s.endswith("compile class") for s in skipped)
    a, b = summary["phase_a"][0], summary["phase_b"]
    assert a["rows"] == b["rows"] == 65536
    assert a["cold_labels"]["cold_build"] == "device"
    assert a["queries"]["after_write"]["device_feed"] == "patch"
    # the warm GROUP BY and Q1-shaped reads: one probe a find, their
    # DAGs' keys carried, none walked (/health fastpath.find / keys)
    assert a["fastpath_warm"] == {
        "hash_agg": {"finds": 6, "probes": 6, "carried": 6, "walked": 0,
                     "hit": 6},
        "q1": {"finds": 1, "probes": 1, "carried": 1, "walked": 0,
               "hit": 1}}


def test_without_the_flag_a_cpu_machine_fails_the_platform_check():
    r = _run(timeout=300)
    assert r.returncode != 0
    assert "platform check" in r.stderr, r.stderr[-2000:]
    assert r.stdout.strip() == ""       # no result line at all


def test_with_device_store_refuses_an_unasked_for_cpu_fallback():
    """``tikv --with-device`` where JAX finds no accelerator and the
    operator did not choose JAX_PLATFORMS=cpu: exit non-zero before
    serving, naming the reason."""
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run(
        [sys.executable, "-m", "tikv_tpu.server", "tikv", "--with-device",
         "--pd", "127.0.0.1:1", "--addr", "127.0.0.1:0"],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert "device runner: platform=cpu" in r.stdout, r.stdout
    # the same line says whether the hash-agg finalize is the native
    # call (no silent fallback to the numpy chain)
    from tikv_tpu import native
    built = "yes" if native.hash_finalize_packed is not None else "no"
    # ... whether a fast-path reply's rows are the native call's
    enc = "yes" if native.encode_rows_msgpack is not None else "no"
    # ... and whether the GIL probe samples through the extension
    from tikv_tpu.utils.trace import gil_mode
    assert f" native_finalize={built} native_encode={enc} " \
        f"gil_probe={gil_mode()} mux=raw\n" in r.stdout, r.stdout
    assert "found no accelerator" in r.stderr, r.stderr[-2000:]
