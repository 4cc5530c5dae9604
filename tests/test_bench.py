"""The driver contract: on a TPU `python bench.py` prints ONE parseable JSON
line with metric/value/unit/vs_baseline keys; without one it prints no
record and exits non-zero. The measurement child (`--inner`) is exercised
end-to-end with a tiny model on the CPU backend via the BENCH_* env
overrides, where it has no chip peak and so no MFU value."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_bench():
    spec = importlib.util.spec_from_file_location(
        "bench", os.path.join(REPO, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# slow: a subprocess paying a full JAX import and fresh jit compiles — the
# `slow` marker is defined for exactly this.
@pytest.mark.slow
def test_inner_emits_json_record_without_mfu_on_cpu():
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu",
        BENCH_MODEL="gpt-nano", BENCH_SEQ="32", BENCH_BATCHES="4",
        BENCH_SERVING="0",  # the serving extra has its own (slow) test
    )
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--inner"],
        capture_output=True, text=True, timeout=900, env=env, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [l for l in proc.stdout.strip().splitlines() if l.strip()]
    assert len(lines) == 1, proc.stdout
    rec = json.loads(lines[0])
    for key in ("metric", "value", "unit", "vs_baseline"):
        assert key in rec, rec
    assert "error" not in rec, rec
    assert rec["paths"], rec
    assert rec["tokens_per_sec_per_chip"] > 0, rec
    # a CPU has no entry in the peak table: throughput, but no utilization
    assert rec["value"] is None and rec["peak_source"] is None, rec


def test_no_tpu_means_no_record_and_nonzero_exit(monkeypatch, capsys):
    """A CPU number is never written under the chip's metric name: when
    the probe finds anything but a TPU, main() starts no measurement,
    prints no record and fails."""
    bench = _load_bench()
    monkeypatch.setattr(bench.subprocess, "run", lambda *a, **k: pytest.fail(
        "a measurement child was started without a TPU"))
    for probe in ({"platform": "cpu", "kind": "cpu", "n": 8},
                  {"error": "backend probe timed out after 240s"}):
        monkeypatch.setattr(bench, "_probe_backend", lambda p=probe: p)
        assert bench.main() != 0
        out = capsys.readouterr()
        assert out.out == ""
        assert "needs a TPU" in out.err


def test_failed_measurement_exits_nonzero(monkeypatch, capsys):
    """The exit code is the measurement's: an error record, or no record
    at all, is a failure — not a 0 with a null value."""
    bench = _load_bench()
    monkeypatch.setattr(bench, "_probe_backend", lambda: {
        "platform": "tpu", "kind": "TPU v5 lite", "n": 1})
    monkeypatch.setattr(sys, "argv", ["bench.py"])

    def child(stdout, rc=0):
        return lambda *a, **k: subprocess.CompletedProcess(
            args=[], returncode=rc, stdout=stdout, stderr="")

    good = json.dumps({"metric": bench.METRIC, "value": 0.4})
    monkeypatch.setattr(bench.subprocess, "run", child(good + "\n"))
    assert bench.main() == 0
    assert json.loads(capsys.readouterr().out) == json.loads(good)

    bad = json.dumps(bench._error_record("all attention paths failed"))
    monkeypatch.setattr(bench.subprocess, "run", child(bad + "\n", rc=1))
    assert bench.main() != 0
    monkeypatch.setattr(bench.subprocess, "run", child("Traceback ...\n", 1))
    assert bench.main() != 0


def test_throughput_honesty_check_rejects_impossible_numbers():
    """If a timed window ever fails to wait for the device, the implied
    TFLOP rate exceeds chip peak and the bench must fail loudly, not
    report it."""
    bench = _load_bench()
    peak = 197e12
    fpt = 1e9  # ~GPT-2-ish flops/token at seq 1024
    # plausible: 0.35 MFU worth of throughput passes
    bench.check_throughput_plausible(0.35 * peak / fpt, fpt, peak)
    # exactly at slack boundary passes; beyond it raises
    with pytest.raises(RuntimeError, match="implausible throughput"):
        bench.check_throughput_plausible(5.0 * peak / fpt, fpt, peak)
    # unknown chip (no peak table entry) can't be checked — no raise
    bench.check_throughput_plausible(1e12, fpt, None)


def test_probe_subprocess_classifies_its_own_exception():
    """The probe's in-subprocess except-hook emits structured JSON (error +
    etype) instead of a traceback."""
    bench = _load_bench()
    probe = bench._probe_backend.__wrapped__ if hasattr(
        bench._probe_backend, "__wrapped__") else bench._probe_backend
    import unittest.mock as mock

    # simulate the subprocess printing the structured error record
    fake = subprocess.CompletedProcess(
        args=[], returncode=0,
        stdout='{"error": "boom", "etype": "ImportError"}\n', stderr="")
    with mock.patch.object(bench.subprocess, "run", return_value=fake):
        out = probe()
    assert out == {"error": "boom", "etype": "ImportError"}


def test_decode_roofline_guard():
    """VERDICT r3 next #8: the decode extra refuses rates that imply more
    parameter-streaming bandwidth than the chip's HBM can deliver."""
    bench = _load_bench()
    peak_bw = 819e9  # v5e
    param_bytes = 2 * 124e6  # GPT-2 124M in bf16
    # plausible: 2000 steps/s x 248 MB params = 496 GB/s < 819 GB/s
    bench.check_decode_plausible(8 * 2000, 8, param_bytes, peak_bw)
    # implausible: 100k steps/s x 248 MB ~= 24.8 TB/s >> 1.5x bandwidth
    with pytest.raises(RuntimeError, match="implausible decode rate"):
        bench.check_decode_plausible(8 * 100_000, 8, param_bytes, peak_bw)
    # unknown chip: no bandwidth table entry — cannot check, no raise
    bench.check_decode_plausible(8 * 100_000, 8, param_bytes, None)


@pytest.mark.slow
def test_serving_probe_shows_admission_cost_scaling():
    """Acceptance (ISSUE 3): the serving probe's compiled-prefill timings
    must show admission cost tracking prompt length — a 16-token bucket
    measurably cheaper than the full window, and a prefix-hit tail no
    more expensive than the same-size fresh prefill."""
    bench = _load_bench()
    rec = bench.serving_probe()
    assert rec["tokens_per_sec"] > 0
    assert rec["prefix_hit_rate"] > 0
    assert rec["prefill_short16_ms"] < rec["prefill_full_window_ms"]
    # the tail after a prefix hit costs ~one small-bucket prefill, not a
    # full-prompt one (generous 2x slack: wall-clock on shared CI boxes)
    assert rec["prefill_prefix_tail_ms"] < 2 * rec["prefill_short16_ms"]
