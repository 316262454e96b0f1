"""The command's look for a chip: without a TPU it fails, names the device
it found and prints no result; it never falls back."""
import os
import subprocess
import sys

from cells import BENCH, ROOT


def test_without_a_tpu_the_run_fails_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "tpch10-cluster.scan-power", "--seed", "4294967295", "--seconds",
         "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr and "'cpu'" in p.stderr


def test_an_unknown_device_kind_is_not_in_the_table_of_peaks():
    from cells import read_json
    peaks = read_json(BENCH, "peaks.json")
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
    assert peaks["TPU v5 lite"]["hbm_bytes"] == 16e9
    assert "source" in peaks["TPU v5 lite"]
    assert peaks.get("TPU v9 imaginary") is None   # run.py exits on None
