"""Driver-artifact contract of bench.py: parseable JSON records that name the
device they ran on, a headline line last — and no way to pass without a
chip except the explicit ``BENCH_PREFLIGHT=1`` CPU gates."""

import json
import os
import subprocess
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STAMP = {"platform", "device_kind", "device_count", "jax_version"}


def _bench(*argv, preflight):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    env.pop("BENCH_PREFLIGHT", None)
    if preflight:
        env["BENCH_PREFLIGHT"] = "1"
    return subprocess.run([sys.executable, os.path.join(REPO, "bench.py"),
                           *argv], capture_output=True, text=True,
                          timeout=900, env=env)


@pytest.mark.slow
def test_bench_full_sweep_streams_records():
    r = _bench(preflight=True)
    assert r.returncode == 0, r.stderr[-2000:]
    records = [json.loads(line) for line in r.stdout.strip().splitlines()]
    by_config = {rec["config"]: rec for rec in records if "config" in rec}
    for config in ("lenet", "resnet50", "lstm", "word2vec", "parallel",
                   "transformer", "longcontext"):
        assert config in by_config, f"no record for {config}"
        rec = by_config[config]
        assert "FAILED" not in rec.get("metric", ""), rec
        assert rec["value"] > 0
        assert STAMP <= set(rec) and rec["platform"] == "cpu"
        assert "mfu" not in rec, "a utilization computed off-chip"
        assert "cached" not in rec and "canonical" not in rec
    headline = records[-1]
    assert {"metric", "value", "unit", "vs_baseline"} <= set(headline)
    assert headline["config"] == "resnet50"


def test_measurement_path_refuses_a_platform_that_is_not_tpu():
    """No BENCH_PREFLIGHT on the CPU: non-zero exit and no record."""
    r = _bench("lenet", preflight=False)
    assert r.returncode != 0
    assert "not 'tpu'" in r.stderr
    records = [json.loads(line) for line in r.stdout.strip().splitlines()]
    assert not any("metric" in rec for rec in records)


def test_a_failed_config_makes_the_exit_code_non_zero(monkeypatch, capsys):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "bench_under_test", os.path.join(REPO, "bench.py"))
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    monkeypatch.setenv("BENCH_PREFLIGHT", "1")
    monkeypatch.setattr(sys, "argv", ["bench.py", "no_such_config"])
    with pytest.raises(SystemExit) as exit_info:
        bench.main()
    assert exit_info.value.code == 1
    records = [json.loads(line)
               for line in capsys.readouterr().out.strip().splitlines()]
    assert records[-1]["metric"] == "no_such_config_FAILED"


def test_peaks_table_is_keyed_by_exact_device_kind():
    from deeplearning4j_tpu.telemetry import devices
    v5e = devices.device_peaks(types.SimpleNamespace(
        platform="tpu", device_kind="TPU v5 lite"))
    assert v5e == {"bf16_flops": 197e12, "int8_ops": 393e12,
                   "hbm_bytes_per_s": 819e9, "hbm_bytes": 16 * 2 ** 30}
    # off-chip: no peaks, so callers omit their utilization fields
    assert devices.device_peaks() is None
    with pytest.raises(ValueError, match="TPU v9 imaginary"):
        devices.device_peaks(types.SimpleNamespace(
            platform="tpu", device_kind="TPU v9 imaginary"))
