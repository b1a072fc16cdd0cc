"""The harness finds cells, traffic mixes, traffic kinds and per-layer
metrics by name: a new traffic file, kind file, metric file and
``BENCHMARK.json`` entry run with no edit to a file that is there.  And
a run without a TPU exits non-zero and prints no result."""
import json
import os
import shutil
import subprocess
import sys

import pytest

import run
from harness import spec

ROOT = run.ROOT


def copy_of_the_benchmark(tmp_path, monkeypatch):
    """A checkout of the benchmark alone under ``tmp_path``, which the
    harness reads in place of this one; its files and their bytes."""
    bench_dir = tmp_path / "benchmark"
    shutil.copytree(run.BENCH, bench_dir,
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    monkeypatch.setattr(spec, "BENCH", bench_dir)
    monkeypatch.setattr(run, "ROOT", tmp_path)
    return bench_dir, {p: p.read_bytes() for p in bench_dir.rglob("*")
                       if p.is_file()}


def test_new_cell_traffic_and_metric_are_found_by_name(tmp_path,
                                                       monkeypatch):
    bench_dir, before = copy_of_the_benchmark(tmp_path, monkeypatch)
    traffic = json.loads((bench_dir / "traffic" / "block_stream.json")
                         .read_text())
    # a mix of data alone: short segments of half-attested blocks
    (bench_dir / "traffic" / "block_pairs.json").write_text(json.dumps(
        {**traffic, "segment_blocks": 3, "settle_imports": 1,
         "attesting_committees": 0.5, "attesting_bits": 0.5,
         "sync_bits": 0.5}))
    (bench_dir / "metrics" / "stf.block_max_ms.py").write_text(
        "def read(ctx):\n"
        "    d = [e - s for k, s, e in ctx.spans"
        " if k == 'state_transition']\n"
        "    return 1000 * max(d) if d else None\n")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "mainnet_1m.block_pairs",
                               "config": "mainnet_1m",
                               "traffic": "block_pairs", "chips": 1,
                               "why": "two-block segments"})
    bench["end_to_end"][0]["workloads"].append("mainnet_1m.block_pairs")
    bench["per_layer"].append({
        "name": "stf.block_max_ms", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "state transition",
        "moves": "block_import_ms", "workloads": ["mainnet_1m.block_pairs"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    loaded = spec.load(tmp_path)
    assert [m["name"] for m in spec.per_layer(
        loaded, "mainnet_1m.block_pairs")] == ["stf.block_max_ms"]
    args = run.parse(["--workload", "mainnet_1m.block_pairs", "--seed",
                      str(2**31 + 9), "--seconds", "2", "--trace", "1"])
    result = run.run_cell(args, loaded, {"config": {"validators": 1 << 14}},
                          require_chip=False)
    assert result["correct"], result["checks"]
    assert result["metrics"]["stf.block_max_ms"]["value"] > 0
    # no device plane in a CPU trace: the device readers report nothing
    assert set(result["metrics"]) == {"stf.block_max_ms"}
    after = {p: p.read_bytes() for p in before}
    assert after == before


ECHO_KIND = """
from harness.window import Outcome


def run(run):
    with run.window() as w:
        n = 0
        while w.open() and n < run.traffic["requests"]:
            n += 1
    return Outcome(metrics={"echo_per_s": n / (w.end - w.start),
                            "setup_s": w.setup_s},
                   attempted=n, failed=0, checks={"lost": (0, 0)},
                   spans=w.spans)
"""


def test_new_traffic_kind_is_found_by_name(tmp_path, monkeypatch):
    bench_dir, before = copy_of_the_benchmark(tmp_path, monkeypatch)
    (bench_dir / "kinds" / "echo.py").write_text(ECHO_KIND)
    (bench_dir / "traffic" / "echo.json").write_text(json.dumps(
        {"kind": "echo", "requests": 1000}))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "mainnet_1m.echo",
                               "config": "mainnet_1m", "traffic": "echo",
                               "chips": 1, "why": "a kind added as a file"})
    bench["end_to_end"].append({
        "name": "echo_per_s", "unit": "1/s", "better": "higher",
        "bound": 0.01, "source": "host_clock",
        "workloads": ["mainnet_1m.echo"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    args = run.parse(["--workload", "mainnet_1m.echo", "--seed", "3",
                      "--seconds", "1"])
    result = run.run_cell(args, spec.load(tmp_path), require_chip=False)
    assert result["correct"] and result["attempted"] == 1000
    assert set(result["metrics"]) == {"echo_per_s", "setup_s"}
    assert {p: p.read_bytes() for p in before} == before


def test_a_metric_split_by_cell_reads_the_file_of_its_stem():
    def file_of(name):
        return spec.reader(name).__code__.co_filename
    assert file_of("device.idle_share.import") == \
        file_of("device.idle_share.gossip") == \
        str(run.BENCH / "metrics" / "device.idle_share.py")
    with pytest.raises(FileNotFoundError):
        spec.reader("no_such.metric")


def test_a_per_layer_metric_without_workloads_is_refused():
    bench = spec.load(ROOT)
    bench["per_layer"].append({"name": "stf.block_max_ms", "unit": "ms",
                               "better": "lower", "source": "program_span",
                               "layer": "state transition",
                               "moves": "block_import_ms"})
    with pytest.raises(SystemExit):
        spec.per_layer(bench, "mainnet_1m.block_stream")


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "mainnet_1m.block_stream", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert '"metrics"' not in proc.stdout


def test_run_with_only_the_benchmark_files_prints_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(run.BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "mainnet_1m.block_stream", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode != 0
    assert '"metrics"' not in proc.stdout


def test_every_name_in_benchmark_json_has_its_file():
    bench = spec.load(ROOT)
    for c in bench["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert json.loads((ROOT / c["file"]).read_text())["name"] == c["name"]
    for w in bench["workloads"]:
        assert spec.traffic(w["traffic"])["kind"]
    for m in bench["per_layer"]:
        assert callable(spec.reader(m["name"]))
