"""The rest of a run, without the harness's look for a chip, with the
timed path broken underneath: an answer altered where the store produces
it has to come out as ``correct: false``; the sound path as true."""

import json
import os

import pytest

import run

ROWS = 32768


def dry_run(tmp_path, workload):
    out = os.path.join(str(tmp_path), workload)
    rc = run.main(["--workload", workload, "--seed", "2147483659",
                   "--seconds", "2", "--trace", "0", "--dry-run-cpu",
                   "--rows", str(ROWS), "--out-dir", out])
    assert rc == 0
    with open(os.path.join(out, "summary.json")) as f:
        return json.load(f)


@pytest.fixture
def altered_answers(monkeypatch):
    """Every third coprocessor reply leaves the store with the first
    row's second value off by one."""
    from tikv_tpu.server import wire
    from tikv_tpu.server.service import KvService
    sound = KvService.handle_raw
    seen = [0]

    def broken(self, method, raw):
        out = sound(self, method, raw)
        seen[0] += 1
        if method != "Coprocessor" or seen[0] % 3:
            return out
        resp = wire.unpack(out) if isinstance(out, bytes) else out
        if resp.get("rows"):
            resp["rows"][0][1] += 1
        return wire.pack(resp) if isinstance(out, bytes) else resp
    monkeypatch.setattr(KvService, "handle_raw", broken)


@pytest.mark.parametrize("workload", ["agg-closed8", "agg-sparse-closed8"])
def test_sound_path_is_correct(tmp_path, workload):
    s = dry_run(tmp_path, workload)
    assert s["correct"] is True and s["failed"] == 0
    assert s["attempted"] > 0


@pytest.mark.parametrize("workload", ["agg-closed8", "agg-sparse-closed8"])
def test_altered_answer_is_not_correct(tmp_path, altered_answers, workload):
    """And the altered replies count as failed, in no latency."""
    s = dry_run(tmp_path, workload)
    assert s["correct"] is False
    assert 0 < s["failed"] < s["attempted"]


def test_without_a_tpu_there_is_no_result_line(tmp_path, capsys):
    rc = run.main(["--workload", "agg-closed8", "--seed", "1", "--seconds",
                   "1", "--trace", "0", "--out-dir", str(tmp_path)])
    assert rc != 0
    assert not capsys.readouterr().out.strip().splitlines()[-1].startswith("{")
